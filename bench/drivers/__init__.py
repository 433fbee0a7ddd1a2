"""One loop per kind of traffic; a mix names its driver under ``driver``."""

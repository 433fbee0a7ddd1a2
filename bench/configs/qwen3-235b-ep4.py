"""Gradient elements one chip holds replicated for one Qwen3-MoE layer.

With attention data-parallel and experts expert-parallel, every chip holds
the whole attention block and the router, and their gradients are summed
over the chips each step:

    q_proj   hidden x heads * head_dim          4096 x 8192
    k_proj   hidden x kv_heads * head_dim       4096 x 512
    v_proj   hidden x kv_heads * head_dim       4096 x 512
    o_proj   heads * head_dim x hidden          8192 x 4096
    router   hidden x experts                   4096 x 128
    norms    input and post-attention RMSNorm (hidden each), q_norm and
             k_norm (head_dim each; Qwen3 normalises queries and keys per head)

Qwen3 attention has no biases (``attention_bias`` false).
"""


def replicated_grad_elems(cfg: dict) -> int:
    hidden, head_dim = cfg["hidden_size"], cfg["head_dim"]
    q = hidden * cfg["num_attention_heads"] * head_dim
    kv = hidden * cfg["num_key_value_heads"] * head_dim
    router = hidden * cfg["num_experts"]
    norms = 2 * hidden + 2 * head_dim
    return q + 2 * kv + q + router + norms

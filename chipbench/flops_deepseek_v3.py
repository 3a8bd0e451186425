"""The yardstick's arithmetic for the DeepSeek-V3-style configuration:
the model FLOPs ONE chip of the stated deployment needs per trained
token, and the operations and bytes its two new kernels need.
Recomputation and padding never count. Plain floats, no device."""


def _forward_flops_per_token(cfg, seq):
    h = cfg["hidden_size"]
    heads, nope, rope = (cfg["num_attention_heads"],
                         cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"])
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    # MLA projections: q, the latent (+ rotary key), its expansion, out
    proj = 2.0 * (h * heads * (nope + rope) + h * (rank + rope)
                  + rank * heads * (nope + dv) + heads * dv * h)
    # causal scores: a token attends to seq / 2 keys on average
    scores = 2.0 * heads * (nope + rope + dv) * seq / 2.0
    attn = proj + scores

    def mlp(width):
        return 2.0 * 3 * h * width
    dense = attn + mlp(cfg["intermediate_size"])
    lo, hi = cfg.get("experts_held") or (0, cfg["n_routed_experts"])
    picks_here = cfg["num_experts_per_tok"] * (hi - lo) \
        / cfg["n_routed_experts"]
    expert = (attn + 2.0 * h * cfg["n_routed_experts"]
              + mlp(cfg["moe_intermediate_size"] * cfg["n_shared_experts"])
              + picks_here * mlp(cfg["moe_intermediate_size"]))
    lo, hi = cfg.get("vocab_held") or (0, cfg["vocab_size"])
    n_dense = cfg["first_k_dense_replace"]
    return (n_dense * dense + (cfg["num_hidden_layers"] - n_dense) * expert
            + 2.0 * h * (hi - lo))


def model_flops_per_token(cfg, seq):
    """Forward + backward FLOPs per trained token of what this chip
    holds (``cfg`` is the configuration file): MLA projections, causal
    scores at the q/k width and the v width, the dense MLP, the shared
    MLP, the router, the routed experts at the EXPECTED ``top_k x held /
    routed`` picks a token, the sliced head; backward = 2 x forward."""
    return 3.0 * _forward_flops_per_token(cfg, seq)


def flash_mla_train(batch, heads, seq, qk_dim, v_dim, layers,
                    dtype_bytes=2):
    """Causal flash attention with q/k of ``qk_dim`` and v of ``v_dim``,
    forward + backward, ``layers`` layers of one step. Forward: QK^T
    (qk) and PV (v). Backward: S again (qk), dP (v), dV (v), dQ (qk),
    dK (qk). Bytes: q, k, v read and o written forward; q, k, v, o, do
    read and dq, dk, dv written backward, each at its own width."""
    half = 2.0 * 0.5 * batch * heads * seq * seq * layers
    ops = half * ((qk_dim + v_dim) + (3 * qk_dim + 2 * v_dim))
    rows = batch * heads * seq * dtype_bytes * layers
    nbytes = rows * ((2 * qk_dim + 2 * v_dim) + (4 * qk_dim + 4 * v_dim))
    return ops, nbytes


def moe_gmm_train(rows, hidden, width, experts, layers, dtype_bytes=2):
    """The grouped expert products over the COUNTED held rows ``rows``
    (summed over ``layers`` layers of one step): gate, up, down forward
    and two products each backward (dx, dw). Bytes: the rows in and out
    of each product, and the ``experts`` held experts' weights read for
    the forward and for dx and their fp32 gradient written."""
    ops = 9.0 * 2.0 * rows * hidden * width
    row_bytes = rows * (2 * hidden + 3 * width) * dtype_bytes * 3
    weights = experts * layers * 3 * hidden * width
    return ops, row_bytes + weights * (2 * dtype_bytes + 4)

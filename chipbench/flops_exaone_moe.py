"""Bytes a verify tick of the K-EXAONE serving cell has to stream, from
shapes and from what the program counted (a file beside ``flops.py``;
the numbers in ``PERF.md`` section 5 and in the configuration's
``sizing`` are these). Plain floats, no device.

The cell's kernels are the repository's own (the paged decode kernel's
verify branch, the page write, the grouped expert products): their
rooflines are reckoned by ``flops_smallthinker.paged_decode_gqa`` and
``flops_smallthinker.moe_gmm_served``, which the accepted metric files
name. This file adds no kernel's arithmetic; it splits the tick's
MODEL bytes the way section 5 discusses them.
"""


def layer_weight_bytes(cfg, touched, sparse=True, weight_bytes=2):
    """One layer's weights a tick reads: attention's four projections
    and two head norms, the layer's two norms, and either the dense MLP
    or the router, the shared expert and the ``touched`` held experts
    (an untouched expert's weights need not be read)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    attn = h * d * 2 * (cfg["num_attention_heads"]
                        + cfg["num_key_value_heads"]) + 2 * d + 2 * h
    expert = 3.0 * h * cfg["moe_intermediate_size"]
    if not sparse:
        return (attn + 3.0 * h * cfg["intermediate_size"]) * weight_bytes
    return (attn + h * cfg["published"]["num_experts"]
            + (cfg["num_shared_experts"] + touched) * expert) * weight_bytes


def mtp_weight_bytes(cfg, touched, weight_bytes=2):
    """The multi-token-prediction block's weights a tick reads: the
    projection of ``[embedding ; hidden]``, a sparse full layer, three
    norms (its head and embedding are the main model's)."""
    h = cfg["hidden_size"]
    return (2.0 * h * h + 3 * h) * weight_bytes \
        + layer_weight_bytes(cfg, touched, True, weight_bytes)


def page_bytes(cfg, kv_tokens_global, kv_tokens_window, global_layers,
               window_layers, kv_bytes=2):
    """K and V rows a tick's attention reads, by class: whole contexts
    on the ``global_layers`` of the page class (the block's among
    them), contexts cut at the window on the ring's; the verify
    window's two queries read each row once."""
    row = 2.0 * cfg["num_key_value_heads"] * cfg["head_dim"] * kv_bytes
    return (kv_tokens_global * global_layers * row,
            kv_tokens_window * window_layers * row)


def tick_bytes(cfg, live_rows, touched, kv_tokens_global,
               kv_tokens_window, weight_bytes=2):
    """``{part: bytes}`` of one verify tick at ``live_rows`` rows with
    ``touched`` held experts read a layer that has experts: the main
    layers' weights, the block's, the head's slice (read twice: the
    block drafts through it, the verify scores through it; the
    embedding's rows are gathers), pages by class."""
    layers = cfg["num_hidden_layers"]
    main = sum(layer_weight_bytes(
        cfg, touched, cfg["mlp_layer_types"][i] == "sparse", weight_bytes)
        for i in range(layers))
    windows = sum(w > 0 for w in cfg["sliding_windows"][:layers])
    blocks = cfg["num_nextn_predict_layers"]
    glob, ring = page_bytes(
        cfg, kv_tokens_global, kv_tokens_window,
        layers - windows + blocks, windows)
    head = cfg["hidden_size"] * cfg["vocab_size"] * weight_bytes
    return {"main_layers": main,
            "mtp_block": blocks * mtp_weight_bytes(cfg, touched,
                                                   weight_bytes),
            "head": (1 + blocks) * head,
            "embedding_rows": (1 + 2 * blocks) * live_rows
            * cfg["hidden_size"] * weight_bytes,
            "pages_global": glob, "pages_window": ring}

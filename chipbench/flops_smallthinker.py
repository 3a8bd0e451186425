"""Operations and bytes the kernels of the SmallThinker serving cell
NEED, from shapes and from what the program counted (a file beside
``flops.py``; reader ``kernel_roofline_in`` reaches it). Plain floats,
no device.
"""


def paged_decode_gqa(kv_tokens_global, kv_tokens_window, kv_heads,
                     q_heads, head_dim, global_layers, window_layers,
                     kv_bytes=2):
    """Decode attention through the paged pool with grouped-query heads.
    ``kv_tokens_global`` is the sum, over the traced decode ticks and
    the slots live in each, of the context a GLOBAL layer attends to;
    ``kv_tokens_window`` the same with every slot's context cut at the
    sliding window (what a window layer may read, whatever blocks its
    walk visits). Each token costs one K row and one V row of
    ``kv_heads x head_dim`` a layer, read ONCE for the whole group of
    query heads; 2 FLOPs per query-head element for QK^T and for PV.
    Memory-bound while a group has under ~240 heads."""
    tokens = kv_tokens_global * global_layers \
        + kv_tokens_window * window_layers
    nbytes = 2.0 * tokens * kv_heads * head_dim * kv_bytes
    ops = 2.0 * 2.0 * tokens * q_heads * head_dim
    return ops, nbytes


def moe_gmm_served(picks, touched, hidden, width, weight_bytes=2,
                   row_bytes=2):
    """The ragged grouped products (gate|up, then down) of the expert
    layers, summed over layers and programs: ``picks`` rows dispatched
    (one a token a picked expert), ``touched`` the distinct experts
    with at least one row, counted a layer and summed (an untouched
    expert's weights need not be read). Bytes: a touched expert's three
    matrices once; a row in (``hidden``), its gate|up out and the
    activation back in (``3 width``), its result out (``hidden``).
    FLOPs: ``2 x 3 x hidden x width`` a row."""
    nbytes = touched * 3.0 * hidden * width * weight_bytes \
        + picks * (2.0 * hidden + 3.0 * width) * row_bytes
    ops = picks * 2.0 * 3.0 * hidden * width
    return ops, nbytes


def tick_model_bytes(live_rows, touched_per_layer, hidden, width, q_heads,
                     kv_heads, head_dim, vocab, layers, weight_bytes=2):
    """Weight bytes one decode tick has to stream: attention's four
    projections and the router a layer, the touched experts, the head
    (the embedding's rows are ``live_rows`` gathers)."""
    attn = hidden * head_dim * (2 * q_heads + 2 * kv_heads)
    per_layer = attn + touched_per_layer * 3.0 * hidden * width
    return (layers * per_layer + hidden * vocab
            + live_rows * hidden) * weight_bytes

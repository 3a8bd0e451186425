"""Operations and bytes the kernels of the Granite hybrid serving cell
NEED, from shapes and from what the program counted (a file beside
``flops.py``; reader ``kernel_roofline_in`` reaches it). Plain floats,
no device. The grouped paged decode is ``flops_smallthinker``'s
(``paged_decode_gqa``): the metric file names it with this
configuration's keys.
"""


def ssd_decode_step(live_rows, ssm_layers, heads, head_dim, state_dim,
                    state_bytes=4, row_bytes=4):
    """The decode step of the state-space layers over the LIVE slots'
    states: ``live_rows`` is the sum, over the traced decode ticks, of
    the slots live in each. A live row's state (``heads x head_dim x
    state_dim`` float32, 2.10 MB) is read once and written once a
    layer; beside it come the row's per-channel operand tile (``dt x``,
    the decay, ``D x``: three rows of ``heads x head_dim`` the
    algorithm needs, whatever padding the kernel's tile carries) and
    ``B`` and ``C`` (``state_dim`` each), and its output row goes out.
    FLOPs: the decay, the rank-one update and the readout, 2 each an
    element of the state. Memory-bound by a factor of ~50."""
    states = float(live_rows) * ssm_layers * heads * head_dim * state_dim
    rows = float(live_rows) * ssm_layers
    nbytes = 2.0 * states * state_bytes + rows * row_bytes * (
        (3 + 1) * heads * head_dim + 2 * state_dim)
    ops = 6.0 * states
    return ops, nbytes


def tick_model_bytes(live_rows, hidden, width, q_heads, kv_heads, head_dim,
                     ssm_heads, ssm_head_dim, state_dim, taps, vocab,
                     kv_layers, ssm_layers, weight_bytes=2):
    """Weight bytes one decode tick has to stream: a softmax layer's
    four projections, a state-space layer's two projections, its
    convolution and its norm, in every layer the fused gated MLP, then
    the tied head over the whole vocabulary (the embedding's rows are
    ``live_rows`` gathers of the same table)."""
    layers = kv_layers + ssm_layers
    inner = ssm_heads * ssm_head_dim
    chans = inner + 2 * state_dim
    softmax = hidden * head_dim * (2 * q_heads + 2 * kv_heads)
    ssm = hidden * (inner + chans + ssm_heads) + inner * hidden \
        + (taps + 1) * chans + inner + 3 * ssm_heads
    mlp = 3.0 * hidden * width
    return (kv_layers * softmax + ssm_layers * ssm + layers * mlp
            + hidden * vocab + live_rows * hidden) * weight_bytes


def cache_bytes(slots, pool_pages, page, kv_layers, kv_heads, head_dim,
                ssm_layers, ssm_heads, ssm_head_dim, state_dim, taps,
                act_bytes=2):
    """``(pages, state)``: bytes of the two cache classes a server of
    ``slots`` slots reserves: the page pool over the layers that hold
    K/V, and a row of float32 state and convolution tail a slot (and
    the null row) on every state-space layer."""
    inner = ssm_heads * ssm_head_dim
    pages = float(pool_pages) * page * 2 * kv_heads * head_dim \
        * kv_layers * act_bytes
    row = inner * state_dim * 4 + (taps - 1) * (inner + 2 * state_dim) \
        * act_bytes
    return pages, float(1 + slots) * ssm_layers * row

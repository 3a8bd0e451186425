"""Operations and bytes the kernels of the Solar-Open2 serving cell
NEED, from shapes and from what the program counted (a file beside
``flops.py``; reader ``kernel_roofline_in`` reaches it). Plain floats,
no device. The expert products and the grouped paged decode are
``flops_smallthinker``'s (``moe_gmm_served``, ``paged_decode_gqa``):
the metric files name them with this configuration's keys.
"""


def kda_decode_step(live_rows, linear_layers, heads, head_dim,
                    state_bytes=4, row_bytes=4):
    """The decode step of the delta rule over the LIVE slots' states:
    ``live_rows`` is the sum, over the traced decode ticks, of the
    slots live in each. A live row's state (``heads x head_dim x
    head_dim`` float32) is read once and written once a layer; beside
    it the row's operand tile (a, k, q, v, b and padding: 8 rows of
    ``head_dim`` a head) comes in and its output row goes out. FLOPs:
    decay, the two products with the state and the rank-one update, 2
    each an element of the state. Memory-bound by a factor of ~60."""
    states = float(live_rows) * linear_layers * heads * head_dim * head_dim
    rows = float(live_rows) * linear_layers * heads * head_dim
    nbytes = 2.0 * states * state_bytes + (8 + 1) * rows * row_bytes
    ops = 7.0 * states
    return ops, nbytes


def tick_model_bytes(live_rows, touched_per_layer, hidden, width, q_heads,
                     kv_heads, head_dim, linear_heads, linear_dim, vocab,
                     kv_layers, linear_layers, weight_bytes=2):
    """Weight bytes one decode tick has to stream: a softmax layer's
    projections and gate, a delta layer's projections, convolution and
    low-rank pairs, in every layer the router, the shared expert and
    the touched held experts, then the head over the slice (the
    embedding's rows are ``live_rows`` gathers)."""
    layers = kv_layers + linear_layers
    rank = linear_dim
    softmax = hidden * head_dim * (3 * q_heads + 2 * kv_heads)
    lin = linear_heads * linear_dim
    delta = 4 * hidden * lin + 4 * 3 * lin + 2 * (hidden * rank
                                                  + rank * lin) \
        + hidden * linear_heads + 2 * lin
    experts = (1.0 + touched_per_layer) * 3.0 * hidden * width
    return (kv_layers * softmax + linear_layers * delta
            + layers * experts + hidden * vocab
            + live_rows * hidden) * weight_bytes

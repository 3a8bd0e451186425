"""The blocks four served families share, each held directly against
plain ``jax.numpy``, where they live today.

``models/smallthinker/model.py`` holds ``RMSNorm``, ``apply_rope``,
``window_table`` and the grouped-query ``Attention`` that Solar-Open2,
Granite and K-EXAONE also build on; ``models/solar_open2/model.py`` the
shared-and-routed expert layer and the helpers of a recurrent layer's
state row; ``models/granite_hybrid/model.py`` the dense gated MLP;
``models/gpt/model.py`` the recompute policies (ROADMAP Design 1: they
are a layer below the families in fact, not yet in the tree). The
families' own tests reach them only through whole-model parity with a
float32 reference. Here each is held at the flag sets the families pass
it (``Attention`` with the output gate of Solar-Open2, the query scale
of Granite, the QK-norm of K-EXAONE; window layers with rotary
positions and global ones without), through a chunked paged prefill and
a paged decode tick, on a config that is no family's: the blocks are
duck-typed on the keys they read.

Float32 everywhere; a block and its oracle differ by the order of their
sums (readings 1e-6 .. 3e-5 at these sizes).
"""

import dataclasses
import os

os.environ.setdefault("PFX_PALLAS_INTERPRET", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from paddlefleetx_tpu.models.gpt.model import _remat_policy  # noqa: E402
from paddlefleetx_tpu.models.granite_hybrid.model import (  # noqa: E402
    GatedMLP,
)
from paddlefleetx_tpu.models.smallthinker import model as blk  # noqa: E402
from paddlefleetx_tpu.models.solar_open2 import model as state  # noqa: E402
from paddlefleetx_tpu.models.solar_open2.model import (  # noqa: E402
    SharedAndRoutedExperts,
)
from paddlefleetx_tpu.ops.pallas.flash_attention import (  # noqa: E402
    NULL_PAGE,
)

TOL = 2e-4


# -- Attention ------------------------------------------------------------

PAGE, WINDOW, RING, PAGES = 128, 160, 3, 4


@dataclasses.dataclass(frozen=True)
class Cfg:
    """No family's config: the keys the blocks read."""
    dtype: str = "float32"
    param_dtype: str = "float32"
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.2
    hidden_size: int = 32
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    rope_theta: float = 10000.0
    sliding_window_size: int = WINDOW
    use_flash_attention: bool = True
    kv_page_size: int = PAGE
    kv_pool_pages: int = 1 + PAGES
    window_pool_pages: int = 1 + RING
    max_kv_pages: int = PAGES
    window_ring_pages: int = RING
    cache_capacity: int = PAGES * PAGE
    # the feed-forward blocks'
    shared_intermediate_size: int = 48
    held_experts: tuple = (2, 6)
    n_routed_experts: int = 8
    num_experts_per_tok: int = 3
    moe_intermediate_size: int = 24
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5


CFG = Cfg()

#: the flags each served family passes ``Attention``
FAMILY_FLAGS = {
    "smallthinker": dict(rope=True, window=True),
    "solar_open2": dict(rope=False, window=False, gate=True),
    "granite_hybrid": dict(rope=False, window=False, query_scale=0.25),
    "exaone_moe": dict(rope=True, window=True, qk_norm=True),
}


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate-half by complex multiplication: pair (i, i + d/2) is one
    complex number turned by ``position * theta^(-2i/d)``."""
    d = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    ang = pos[:, None] * theta ** (-jnp.arange(0, d, 2) / d)[None, :]
    z = (x[..., :d // 2] + 1j * x[..., d // 2:]) \
        * jnp.exp(1j * ang)[None, :, None, :]
    return jnp.concatenate([z.real, z.imag], axis=-1)


def _attention_oracle(p, h, flags, cfg):
    """Plain causal (windowed) softmax attention of the whole sequence
    ``h [1, s, hidden]``."""
    nh, g, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim)
    q = jnp.einsum("bsh,hnd->bsnd", h, p["q_proj"]["kernel"])
    k = jnp.einsum("bsh,hgd->bsgd", h, p["k_proj"]["kernel"])
    v = jnp.einsum("bsh,hgd->bsgd", h, p["v_proj"]["kernel"])
    if flags.get("qk_norm"):
        q = _rms(q, p["q_norm"]["scale"], cfg.rms_norm_eps)
        k = _rms(k, p["k_norm"]["scale"], cfg.rms_norm_eps)
    if flags["rope"]:
        q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    q = q * flags.get("query_scale", 1.0)
    k, v = (jnp.repeat(a, nh // g, axis=2) for a in (k, v))
    s = h.shape[1]
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k) * d ** -0.5
    at, key = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = key <= at
    if flags["window"]:
        seen &= key > at - cfg.sliding_window_size
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bnqk,bknd->bqnd", probs, v)
    if flags.get("gate"):
        out = out * jax.nn.sigmoid(
            jnp.einsum("bsh,hnd->bsnd", h, p["gate_proj"]["kernel"]))
    return jnp.einsum("bsnd,ndh->bsh", out, p["o_proj"]["kernel"])


def _tables():
    """One slot's row as the server lays it out: ``PAGES`` global
    columns (shuffled pages), then its ring's ``RING`` page ids."""
    row = np.array([[3, 1, 4, 2] + [2, 3, 1]], np.int32)
    return blk.window_table(jnp.asarray(row), CFG)


@pytest.fixture(scope="module", params=sorted(FAMILY_FLAGS))
def attention(request):
    """``(flags, module, params, h, want)`` for one family's flags: a
    sequence of three chunks and a token, and the oracle's output."""
    flags = FAMILY_FLAGS[request.param]
    module = blk.Attention(CFG, **flags)
    s = 3 * PAGE + 1
    h = jax.random.normal(jax.random.key(3), (1, s, CFG.hidden_size))
    pos = jnp.arange(s)[None, :]
    params = module.init({"params": jax.random.key(1)}, h[:, :8],
                         pos[:, :8])["params"]
    want = _attention_oracle(params, h, flags, CFG)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    return flags, module, params, h, want


def _prefill(module, params, h, chunks):
    """The first ``chunks`` pages of ``h`` through the paged path, a
    page a call; ``(outputs, cache)``."""
    cache = module.init(
        {"params": jax.random.key(0)}, h[:, :PAGE], jnp.arange(PAGE)[None],
        use_cache=True, tables=_tables(), chunk_start=jnp.zeros((1,)))["cache"]
    cache = jax.tree.map(jnp.zeros_like, cache)
    outs = []
    for c in range(chunks):
        at = slice(c * PAGE, (c + 1) * PAGE)
        out, mut = module.apply(
            {"params": params, "cache": cache}, h[:, at],
            jnp.arange(c * PAGE, (c + 1) * PAGE)[None], use_cache=True,
            tables=_tables(), chunk_start=jnp.array([c * PAGE]),
            mutable=["cache"])
        cache = mut["cache"]
        outs.append(out)
    return jnp.concatenate(outs, axis=1), cache


def test_attention_chunked_paged_prefill(attention):
    """Three chunks through the pages (a window layer's through its
    ring) against one pass of plain attention; the dense path too."""
    flags, module, params, h, want = attention
    got, cache = _prefill(module, params, h, 3)
    np.testing.assert_allclose(got, want[:, :3 * PAGE], atol=TOL, rtol=0)
    names = {"window_key", "window_value"} if flags["window"] else \
        {"cached_key", "cached_value"}
    assert set(cache) == names
    dense = module.apply({"params": params}, h, jnp.arange(h.shape[1])[None])
    np.testing.assert_allclose(dense, want, atol=TOL, rtol=0)


def test_attention_paged_decode_tick(attention):
    """After three chunks, the token at position 384 as a decode tick:
    written into page 3 (a window layer's ring page 0, over the page the
    window has left) and read back through the decode kernel."""
    flags, module, params, h, want = attention
    _, cache = _prefill(module, params, h, 3)
    n = 3 * PAGE
    got, _ = module.apply(
        {"params": params, "cache": cache}, h[:, n:], jnp.array([[n]]),
        use_cache=True, cache_lengths=jnp.array([n]), tables=_tables(),
        mutable=["cache"])
    np.testing.assert_allclose(got, want[:, n:], atol=TOL, rtol=0)


def test_rope_is_a_rotation_of_pairs():
    x = jax.random.normal(jax.random.key(0), (2, 9, 3, 16))
    pos = jnp.broadcast_to(jnp.arange(9)[None], (2, 9))
    np.testing.assert_allclose(blk.apply_rope(x, pos, 1e4), _rope(x, 1e4),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(jnp.linalg.norm(blk.apply_rope(x, pos, 1e4),
                                               axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_rms_norm_against_numpy():
    x = jax.random.normal(jax.random.key(2), (3, 5, 32)) * 4.0
    norm = blk.RMSNorm(CFG)
    params = {"scale": jnp.linspace(0.5, 2.0, 32)}
    want = np.asarray(x) / np.sqrt(
        np.mean(np.square(np.asarray(x)), -1, keepdims=True) + 1e-6) \
        * np.asarray(params["scale"])
    np.testing.assert_allclose(norm.apply({"params": params}, x), want,
                               atol=1e-5, rtol=0)


def test_window_table_lays_the_ring_under_every_logical_page():
    """Logical page ``j`` of a window layer is ring page ``j % ring``; a
    free slot's row is dead in both classes; a table of global columns
    alone (shapes only) has no ring."""
    pt = jnp.asarray([[9, 8, 7, 6, 21, 22, 23],
                      [NULL_PAGE] * 4 + [31, 32, 33],
                      [5, NULL_PAGE, NULL_PAGE, NULL_PAGE, 41, 42, 43]],
                     jnp.int32)
    glob, win = blk.window_table(pt, CFG)
    assert glob.tolist() == [[9, 8, 7, 6], [0, 0, 0, 0], [5, 0, 0, 0]]
    assert win.tolist() == [[21, 22, 23, 21], [0, 0, 0, 0], [41, 0, 0, 0]]
    glob, win = blk.window_table(pt[:, :PAGES], CFG)
    assert glob.tolist() == pt[:, :PAGES].tolist() and not win.any()


# -- a recurrent layer's row ------------------------------------------------

class _Leaf:
    """What ``flax``'s ``Variable`` is to the helpers: a ``value``."""

    def __init__(self, value):
        self.value = value


@pytest.mark.parametrize("valid,bias", [(64, False), (37, True)],
                         ids=["whole-chunk", "padded-tail-and-bias"])
def test_conv_tail_and_state_carry_across_a_chunk_boundary(valid, bias):
    """Two chunks and a decode tick through ``carried_in`` /
    ``keep_chunk`` / ``keep_tick_tail`` equal one pass of the
    convolution: the second chunk starts where the first one's REAL
    tokens ended, a chunk that starts a sequence starts from nothing
    whatever the row held, and a tick leaves a dead slot's row alone."""
    taps, chans, length, slots = 4, 6, 64, 3
    rng = np.random.default_rng(valid)
    x = jnp.asarray(rng.normal(size=(1, 2 * length + 1, chans)), jnp.float32)
    weight = jnp.asarray(rng.normal(size=(taps, chans)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(chans,)), jnp.float32) if bias else None
    whole = state.short_conv(
        jnp.concatenate([jnp.zeros((1, taps - 1, chans)), x], axis=1),
        weight, b)
    # by hand at one position
    at = 11
    hand = sum(weight[j] * x[0, at - (taps - 1) + j] for j in range(taps))
    np.testing.assert_allclose(
        whole[0, at], jax.nn.silu(hand + (0 if b is None else b)),
        atol=1e-5, rtol=0)

    # the slot's row holds its last tenant's leftovers
    s_leaf = _Leaf(jnp.full((1 + slots, 2, 5), 7.0))
    tail = _Leaf(jnp.full((1 + slots, (taps - 1) * chans), 7.0))
    rows = jnp.array([2])
    got = []
    for start in (0, valid):
        before, s0 = state.carried_in(s_leaf, tail, rows, taps,
                                      jnp.array([start]))
        if start == 0:
            assert not before.any() and not s0.any()
        else:
            np.testing.assert_array_equal(s0, jnp.full((1, 2, 5), 1.5))
        seq = jnp.concatenate([before, x[:, start:start + length]], axis=1)
        got.append(state.short_conv(seq, weight, b))
        state.keep_chunk(s_leaf, tail, rows, jnp.full((1, 2, 5), 1.5), seq,
                         jnp.array([valid]), taps)
    np.testing.assert_allclose(got[0][:, :valid], whole[:, :valid],
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(got[1], whole[:, valid:valid + length],
                               atol=1e-5, rtol=0)
    assert float(s_leaf.value[1].min()) == 7.0      # another slot's row

    # a tick: batch row i is slot i; slot 1 (row 2) is live, the others
    # are not (row 0) and keep what they hold
    n = 2 * valid
    before, s0 = state.carried_in(s_leaf, tail, jnp.array([0, 2, 0]), taps)
    assert s0 is None
    seq = jnp.concatenate(
        [before, jnp.broadcast_to(x[:, n:n + 1], (slots, 1, chans))], axis=1)
    tick = state.short_conv(seq, weight, b)
    np.testing.assert_allclose(tick[1], whole[0, n:n + 1], atol=1e-5, rtol=0)
    held = tail.value
    state.keep_tick_tail(tail, jnp.array([0, 2, 0]), seq)
    np.testing.assert_array_equal(tail.value[1], held[1])
    np.testing.assert_array_equal(tail.value[3], held[3])
    np.testing.assert_allclose(
        tail.value[2].reshape(taps - 1, chans), x[0, n - 2:n + 1],
        atol=0, rtol=0)


def test_state_rows_ride_behind_the_page_columns():
    pt = jnp.asarray([[4, 5, 0, 0, 2], [0, 0, 0, 0, 0]], jnp.int32)
    pages, rows = state.state_rows(pt, CFG)
    assert pages.tolist() == [[4, 5, 0, 0], [0, 0, 0, 0]]
    assert rows.tolist() == [2, 0]
    pages, rows = state.state_rows(pt[:, :PAGES], CFG)
    assert pages.shape == (2, PAGES) and rows.tolist() == [0, 0]


# -- the feed-forward blocks ------------------------------------------------

def test_gated_mlp_against_an_einsum():
    u = jax.random.normal(jax.random.key(4), (2, 7, CFG.hidden_size))
    mlp = GatedMLP(CFG)
    p = mlp.init({"params": jax.random.key(5)}, u)["params"]
    f = CFG.shared_intermediate_size
    assert p["input_linear"]["kernel"].shape == (CFG.hidden_size, 2 * f)
    gu = jnp.einsum("bsh,hf->bsf", u, p["input_linear"]["kernel"])
    want = jnp.einsum("bsf,fh->bsh", jax.nn.silu(gu[..., :f]) * gu[..., f:],
                      p["output_linear"]["kernel"])
    np.testing.assert_allclose(mlp.apply({"params": p}, u), want,
                               atol=TOL, rtol=0)


def test_shared_and_routed_experts_against_an_einsum():
    """Sigmoid scores over all 8 experts, top-3 by score plus bias,
    weights normalised and scaled; the sum over the HELD picks (experts
    2-5) of each expert's gated product, every expert computed for
    every token, plus the shared expert; dead rows dispatch nothing."""
    cfg = CFG
    lo, hi = cfg.held_experts
    u = jax.random.normal(jax.random.key(6), (2, 20, cfg.hidden_size))
    layer = SharedAndRoutedExperts(cfg)
    p = layer.init({"params": jax.random.key(7)}, u)["params"]
    p = dict(p, e_score_correction_bias=jnp.linspace(
        -0.3, 0.3, cfg.n_routed_experts))
    x = u.reshape(-1, cfg.hidden_size)
    scores = jax.nn.sigmoid(jnp.dot(x, p["gate"],
                                    precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + p["e_score_correction_bias"],
                           cfg.num_experts_per_tok)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    w = picked / picked.sum(-1, keepdims=True) * cfg.routed_scaling_factor
    f = cfg.moe_intermediate_size
    gu = jnp.einsum("th,ehf->tef", x, p["experts_gate_up"])
    every = jnp.einsum("tef,efh->teh",
                       jax.nn.silu(gu[..., :f]) * gu[..., f:],
                       p["experts_down"])                  # [T, held, h]
    held = (idx >= lo) & (idx < hi)
    mine = jnp.take_along_axis(
        every, jnp.clip(idx - lo, 0, hi - lo - 1)[..., None], axis=1)
    routed = jnp.sum(jnp.where(held[..., None], w[..., None] * mine, 0), 1)
    sgu = jnp.dot(x, p["shared_gate_up"]["kernel"])
    shared = jnp.dot(jax.nn.silu(sgu[:, :f]) * sgu[:, f:],
                     p["shared_down"]["kernel"])
    got, stats = layer.apply({"params": p}, u)
    np.testing.assert_allclose(got.reshape(-1, cfg.hidden_size),
                               routed + shared, atol=TOL, rtol=0)
    assert int(stats[0]) == int(held.sum()) > 0
    assert int(stats[1]) == len(np.unique(np.asarray(idx)[np.asarray(held)]))
    # the first token alone is live: the others' rows are the shared
    # expert's and nothing else
    live = jnp.arange(x.shape[0]) == 0
    got, stats = layer.apply({"params": p}, u, live)
    np.testing.assert_allclose(got.reshape(-1, cfg.hidden_size)[1:],
                               shared[1:], atol=TOL, rtol=0)
    assert int(stats[0]) == int(held[0].sum())


# -- the recompute policies -------------------------------------------------

@pytest.mark.parametrize("granularity", [
    "full", "full_attn", "core_attn", "save_dots", "dots"])
def test_remat_policy_of_each_granularity(granularity):
    """``full`` is no policy (nothing saveable); the three named ones
    are policies ``jax.checkpoint`` takes, under which the gradient is
    the gradient; any other name is refused."""
    from jax.ad_checkpoint import checkpoint_name
    if granularity == "dots":
        with pytest.raises(ValueError):
            _remat_policy(granularity)
        return
    policy = _remat_policy(granularity)
    assert (policy is None) == (granularity == "full")

    def f(x):
        a = checkpoint_name(x * x, "attn")
        c = checkpoint_name(jnp.sin(a), "core_attn")
        return jnp.sum(checkpoint_name(c * a, "mlp1") ** 2)

    x = jnp.linspace(0.1, 1.3, 7)
    np.testing.assert_allclose(
        jax.grad(jax.checkpoint(f, policy=policy))(x), jax.grad(f)(x),
        rtol=1e-6)

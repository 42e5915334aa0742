"""Flash-attention Pallas kernels (fwd + tiled bwd) vs the XLA reference.

Runs the real kernels in Pallas interpret mode on the CPU mesh (the module
auto-selects interpret off-TPU), pinning forward outputs and dq/dk/dv/dbias
to the reference attention to tight f32 tolerance. Ref for semantics:
TransformerLayer.scala:50, BERT.scala:60 (additive padding mask).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import inner_jaxprs

from analytics_zoo_tpu.ops.attention import (_reference_attention,
                                             scaled_dot_product_attention)
from analytics_zoo_tpu.ops.flash_attention import flash_attention

B, N, S, D = 2, 2, 256, 64
TOL = dict(rtol=2e-3, atol=2e-3)


def _qkv(key, s_q=S, s_k=S):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, N, s_q, D), jnp.float32)
    k = jax.random.normal(kk, (B, N, s_k, D), jnp.float32)
    v = jax.random.normal(kv, (B, N, s_k, D), jnp.float32)
    return q, k, v


def _padding_bias(key, s_k=S):
    # BERT-style: last ~quarter of keys masked per batch row, (B,1,1,S)
    lens = jax.random.randint(key, (B,), 3 * s_k // 4, s_k)
    mask = (jnp.arange(s_k)[None, :] < lens[:, None]).astype(jnp.float32)
    return (1.0 - mask[:, None, None, :]) * -1e9


def _check_fwd_and_grads(q, k, v, bias, causal, called=lambda f: f,
                         window=None):
    scale = D ** -0.5

    def flash(*arrays, bias=None):
        # `called` sees arrays only: the mask flag and the scale stay static
        return called(lambda *a, bias: flash_attention(
            *a, bias=bias, causal=causal, scale=scale,
            window=window))(*arrays, bias=bias)

    def reference(q_, k_, v_, b_):
        return _reference_attention(q_, k_, v_, b_, causal, scale,
                                    window=window)

    # a causal query before the first key (s_q > s_k) sees none: the kernels
    # give it 0, the reference the mean of v; nobody reads either
    s_q, s_k = q.shape[2], k.shape[2]
    seen = (jnp.arange(s_q) >= (s_q - s_k if causal else 0))[:, None]
    out_f = flash(q, k, v, bias=bias)
    out_r = reference(q, k, v, bias)
    np.testing.assert_allclose(out_f * seen, out_r * seen, **TOL)

    g = jax.random.normal(jax.random.PRNGKey(9), out_r.shape,
                          jnp.float32) * seen

    if bias is None:
        def loss_f(q_, k_, v_):
            return jnp.vdot(flash(q_, k_, v_), g)

        def loss_r(q_, k_, v_):
            return jnp.vdot(reference(q_, k_, v_, None), g)
        grads_f = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        grads_r = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    else:
        def loss_f(q_, k_, v_, b_):
            return jnp.vdot(flash(q_, k_, v_, bias=b_), g)

        def loss_r(q_, k_, v_, b_):
            return jnp.vdot(reference(q_, k_, v_, b_), g)
        grads_f = jax.grad(loss_f, argnums=(0, 1, 2, 3))(q, k, v, bias)
        grads_r = jax.grad(loss_r, argnums=(0, 1, 2, 3))(q, k, v, bias)

    for gf, gr, name in zip(grads_f, grads_r, "q k v bias".split()):
        np.testing.assert_allclose(gf, gr, err_msg=f"d{name}", **TOL)


# How a caller that keeps no named residual reaches the kernels: as is,
# inside a bare jax.checkpoint (TransformerLayer(remat=True)), under jax.jit
# with no checkpoint (ring attention, serving).
_CALLED = {"plain": lambda f: f, "checkpoint": jax.checkpoint, "jit": jax.jit}


def _reference_with_lse(q, k, v, causal):
    logits = jnp.einsum("bnqd,bnkd->bnqk", q, k) * D ** -0.5
    if causal:
        logits = jnp.where(jnp.tril(jnp.ones(logits.shape[-2:], bool)),
                           logits, -jnp.inf)
    return (_reference_attention(q, k, v, None, causal, D ** -0.5),
            jax.nn.logsumexp(logits, axis=-1))


def _out_lse_and_grads(attn, q, k, v):
    """``attn(q, k, v)``'s (out, lse) and the gradients of a loss that reads
    both, so that lse's cotangent reaches q and k."""
    g = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)
    g_lse = jax.random.normal(jax.random.PRNGKey(10), q.shape[:3], jnp.float32)

    def loss(q_, k_, v_):
        out, lse = attn(q_, k_, v_)
        return jnp.vdot(out, g) + jnp.vdot(lse, g_lse), (out, lse)

    grads, outs = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (*outs, *grads)


@pytest.mark.parametrize("called", list(_CALLED))
@pytest.mark.parametrize("causal", [False, True])
def test_flash_no_bias(causal, called, monkeypatch):
    """Against the reference, the log-sum-exp and its cotangent included;
    and where no checkpoint carries a policy the residuals' names
    (``FLASH_RESIDUALS``) change nothing, bit for bit."""
    from analytics_zoo_tpu.ops import flash_attention as fa

    q, k, v = _qkv(jax.random.PRNGKey(0))
    _check_fwd_and_grads(q, k, v, None, causal, _CALLED[called])

    def with_lse():   # traced anew each time, so that the patch below shows
        return _CALLED[called](lambda *a: fa.flash_attention_with_lse(
            *a, causal=causal))

    named = _out_lse_and_grads(with_lse(), q, k, v)
    want = _out_lse_and_grads(
        lambda *a: _reference_with_lse(*a, causal), q, k, v)
    for a, b, what in zip(named, want, "out lse dq dk dv".split()):
        np.testing.assert_allclose(a, b, err_msg=what, **TOL)
    jaxpr = str(jax.make_jaxpr(
        lambda *a: _out_lse_and_grads(with_lse(), *a))(q, k, v))
    assert all(name in jaxpr for name in fa.FLASH_RESIDUALS)
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    for a, b in zip(named, _out_lse_and_grads(with_lse(), q, k, v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_padding_mask(causal):
    q, k, v = _qkv(jax.random.PRNGKey(1))
    bias = _padding_bias(jax.random.PRNGKey(2))
    _check_fwd_and_grads(q, k, v, bias, causal)


def test_flash_dense_bias_grad():
    # smooth per-head bias (B,N,1,S): checks the dbias accumulation path
    q, k, v = _qkv(jax.random.PRNGKey(3))
    bias = jax.random.normal(jax.random.PRNGKey(4), (B, N, 1, S), jnp.float32)
    _check_fwd_and_grads(q, k, v, bias, causal=False)


def test_flash_cross_lengths_causal():
    # s_q != s_k exercises the bottom-right causal offset in fwd and bwd
    q, k, v = _qkv(jax.random.PRNGKey(5), s_q=128, s_k=256)
    _check_fwd_and_grads(q, k, v, None, causal=True)


def test_flash_full_rank_bias_falls_back():
    q, k, v = _qkv(jax.random.PRNGKey(6))
    bias = jnp.zeros((B, N, S, S))
    with pytest.raises(NotImplementedError):
        flash_attention(q, k, v, bias=bias)
    # dispatcher silently takes the XLA path
    out = scaled_dot_product_attention(q, k, v, bias=bias, use_flash=True)
    np.testing.assert_allclose(
        out, _reference_attention(q, k, v, bias, False, D ** -0.5), **TOL)


def test_bert_mask_stays_on_fast_path():
    """The BERT padding-mask layout must NOT fall back (VERDICT #5)."""
    q, k, v = _qkv(jax.random.PRNGKey(7))
    bias = _padding_bias(jax.random.PRNGKey(8))
    # would raise NotImplementedError (and the dispatcher would swallow it)
    # if the (B,1,1,S) layout were unsupported — call the kernel directly
    out = flash_attention(q, k, v, bias=bias)
    ref = _reference_attention(q, k, v, bias, False, D ** -0.5)
    np.testing.assert_allclose(out, ref, **TOL)


def test_flash_bf16_matmul_strategy():
    """bf16 inputs run bf16 MXU matmuls with f32 accumulation (the XLA
    parity strategy); outputs/grads must track the f32 reference within
    bf16 resolution."""
    q, k, v = _qkv(jax.random.PRNGKey(12))
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))
    scale = D ** -0.5
    out_b = flash_attention(qb, kb, vb, causal=True, scale=scale)
    assert out_b.dtype == jnp.bfloat16
    out_r = _reference_attention(q, k, v, None, True, scale)
    np.testing.assert_allclose(np.asarray(out_b, np.float32), out_r,
                               rtol=2e-2, atol=2e-2)

    g = jax.random.normal(jax.random.PRNGKey(13), out_r.shape, jnp.float32)

    def loss_b(q_, k_, v_):
        return jnp.vdot(flash_attention(q_, k_, v_, causal=True,
                                        scale=scale).astype(jnp.float32), g)

    def loss_r(q_, k_, v_):
        return jnp.vdot(_reference_attention(q_, k_, v_, None, True, scale), g)

    gb = jax.grad(loss_b, argnums=(0, 1, 2))(qb, kb, vb)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for b_, r_, name in zip(gb, gr, "q k v".split()):
        assert b_.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(b_, np.float32), r_,
                                   rtol=6e-2, atol=6e-2, err_msg=f"d{name}")


def test_block_env_validation():
    """AZOO_FLASH_BLOCK_Q/K must be positive multiples of 128 — a bad value
    should fail with a clear message naming the env var, not deep inside
    the Mosaic lowering (ADVICE r4 #2)."""
    from analytics_zoo_tpu.ops.flash_attention import _block_env

    assert _block_env("AZOO_FLASH_TEST_UNSET", 256) == 256
    for bad in ("96", "0", "-128", "banana", "12.5"):
        os.environ["AZOO_FLASH_TEST_BAD"] = bad
        try:
            with pytest.raises(ValueError, match="AZOO_FLASH_TEST_BAD"):
                _block_env("AZOO_FLASH_TEST_BAD", 128)
        finally:
            del os.environ["AZOO_FLASH_TEST_BAD"]


def test_per_call_block_sizes_match_default():
    """flash_attention(block_q=, block_k=) — the in-process autotune sweep
    path — must be numerically identical to the default tiling, and reject
    non-tile values with the clear error."""
    rng = np.random.default_rng(11)
    b, h, s, d = 1, 2, 256, 32
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    base = flash_attention(q, k, v, causal=True)
    for bq, bk in ((256, 128), (128, 256), (256, 256)):
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   atol=1e-5, err_msg=f"{bq}x{bk}")
    g = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)

    def loss(bq, bk):
        return jax.grad(lambda q_: jnp.vdot(flash_attention(
            q_, k, v, causal=True, block_q=bq, block_k=bk), g))(q)

    np.testing.assert_allclose(np.asarray(loss(256, 256)),
                               np.asarray(loss(None, None)), atol=1e-4)
    with pytest.raises(ValueError, match="multiple of 128"):
        flash_attention(q, k, v, block_q=96)


def test_seq_aware_default_tiles(monkeypatch):
    """With no per-call arg and no env pin, the default tiling is 512 on
    any sequence axis divisible by 512 (the r5 on-chip sweep winner at
    seq>=2048 on both passes) and the 128 floor otherwise; an explicit
    AZOO_FLASH_BLOCK_Q/K pin wins over the heuristic. The env is read
    PER CALL (ADVICE r5 low): setting or unsetting it after import takes
    effect on the next dispatch."""
    import analytics_zoo_tpu.ops.flash_attention as fa

    monkeypatch.delenv("AZOO_FLASH_BLOCK_Q", raising=False)
    monkeypatch.delenv("AZOO_FLASH_BLOCK_K", raising=False)
    assert fa._resolve_blocks(None, None, 2048, 4096) == (512, 512)
    assert fa._resolve_blocks(None, None, 512, 512) == (512, 512)
    assert fa._resolve_blocks(None, None, 256, 2048) == (128, 512)
    assert fa._resolve_blocks(None, None, 2048, 384) == (512, 128)
    # per-call args always win
    assert fa._resolve_blocks(256, 128, 2048, 2048) == (256, 128)
    # an env pin beats the heuristic (operators tune per workload) — and
    # is honored post-import, not captured once at module load
    monkeypatch.setenv("AZOO_FLASH_BLOCK_Q", "256")
    monkeypatch.setenv("AZOO_FLASH_BLOCK_K", "256")
    assert fa._resolve_blocks(None, None, 2048, 2048) == (256, 256)
    # unsetting restores the seq-aware default immediately
    monkeypatch.delenv("AZOO_FLASH_BLOCK_Q")
    monkeypatch.delenv("AZOO_FLASH_BLOCK_K")
    assert fa._resolve_blocks(None, None, 2048, 2048) == (512, 512)
    # a malformed pin fails with the clear validator error, naming the var
    monkeypatch.setenv("AZOO_FLASH_BLOCK_K", "96")
    with pytest.raises(ValueError, match="AZOO_FLASH_BLOCK_K"):
        fa._resolve_blocks(None, None, 2048, 2048)


def test_auto_dispatch_respects_env_tile_pins(monkeypatch):
    """_auto_use_flash derives its measured-regime check from the tiles
    _resolve_blocks would ACTUALLY pick: with AZOO_FLASH_BLOCK_Q/K pinned
    to 128, a 512-divisible bf16 shape in the 256 MiB-1 GiB band must
    fall back to the conservative 1 GiB bound (the 128-tile kernels lose
    to XLA there — ADVICE r5 low)."""
    import analytics_zoo_tpu.ops.attention as att

    class _Dev:
        platform = "tpu"
    monkeypatch.setattr(att.jax, "devices", lambda: [_Dev()])
    monkeypatch.delenv("AZOO_FLASH_BYTES_THRESHOLD", raising=False)
    monkeypatch.delenv("AZOO_FLASH_BLOCK_Q", raising=False)
    monkeypatch.delenv("AZOO_FLASH_BLOCK_K", raising=False)

    arr = jax.ShapeDtypeStruct((4, 8, 2048, 64), jnp.bfloat16)
    assert att._auto_use_flash(arr, arr)  # 268 MiB, 512 tiles: fast path
    monkeypatch.setenv("AZOO_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("AZOO_FLASH_BLOCK_K", "128")
    assert not att._auto_use_flash(arr, arr)  # pinned 128 tiles: 1 GiB bound
    # past the memory bound flash engages regardless of tiling
    big = jax.ShapeDtypeStruct((4, 8, 4096 + 128, 64), jnp.bfloat16)
    assert att._auto_use_flash(big, big)


def test_auto_dispatch_regime_guard(monkeypatch):
    """The 256 MiB crossover applies only where it was measured (bf16,
    512-divisible seq axes); other dtypes/tilings keep the 1 GiB
    memory-pressure bound, and an explicit env pin applies verbatim."""
    import analytics_zoo_tpu.ops.attention as att

    class _Dev:
        platform = "tpu"
    monkeypatch.setattr(att.jax, "devices", lambda: [_Dev()])
    monkeypatch.delenv("AZOO_FLASH_BYTES_THRESHOLD", raising=False)

    def arr(dtype, s):
        return jax.ShapeDtypeStruct((4, 8, s, 64), dtype)

    bf16, f32 = jnp.bfloat16, jnp.float32
    # bf16 seq 2048 (268 MiB, 512-divisible): fast crossover applies
    assert att._auto_use_flash(arr(bf16, 2048), arr(bf16, 2048))
    # bf16 seq 2176 (303 MiB, NOT 512-divisible -> 128 tiles lose): XLA
    assert not att._auto_use_flash(arr(bf16, 2176), arr(bf16, 2176))
    # f32 seq 2048 (512 MiB, f32 matmuls lose): XLA
    assert not att._auto_use_flash(arr(f32, 2048), arr(f32, 2048))
    # but past the 1 GiB memory bound flash engages regardless
    assert att._auto_use_flash(arr(bf16, 4096 + 128), arr(bf16, 4096 + 128))
    assert att._auto_use_flash(arr(f32, 4096), arr(f32, 4096))
    # an operator pin applies verbatim to every shape
    monkeypatch.setenv("AZOO_FLASH_BYTES_THRESHOLD", str(256 << 20))
    assert att._auto_use_flash(arr(f32, 2048), arr(f32, 2048))
    assert att._auto_use_flash(arr(bf16, 2176), arr(bf16, 2176))


def _brute_force_live(s_q, s_k, block_q, block_k, window):
    """Every (query, key) pair of the causal mask, then by block: live where
    any pair is seen."""
    q_pos = np.arange(s_q)[:, None] + s_k - s_q
    k_pos = np.arange(s_k)[None, :]
    seen = q_pos >= k_pos
    if window is not None:
        seen &= q_pos - k_pos < window
    return seen.reshape(s_q // block_q, block_q,
                        s_k // block_k, block_k).any((1, 3))


@pytest.mark.parametrize("lens,blocks,window", [
    ((512, 512), (128, 128), None),
    ((512, 512), (128, 256), 130),
    ((512, 512), (256, 128), 1),
    ((512, 512), (128, 128), 2048),
    ((384, 640), (128, 128), None),
    ((384, 640), (128, 128), 200),
    ((384, 640), (128, 128), 256),
    ((640, 640), (128, 128), None),
    ((640, 640), (128, 128), 130),
    ((640, 640), (128, 128), 1),
    ((1024, 1024), (128, 256), None),
    ((1024, 1024), (256, 128), 200),
    ((1024, 1024), (128, 256), 256),
    ((1024, 1024), (256, 128), 2048),
    ((640, 384), (128, 128), None),     # queries before the first key
    ((640, 384), (128, 128), 130),
])
def test_the_block_schedule_against_a_brute_force_mask(lens, blocks, window):
    """The schedule is the one place that knows which blocks are live: they
    are the brute-force mask's, every one is a step exactly once (in dk/dv
    once for each head of a group, heads innermost), the streamed index
    ascends within a resident block, the first / last flags bracket it, and
    a resident block with no live partner keeps one step that is not live."""
    from analytics_zoo_tpu.ops import flash_attention as fa

    (s_q, s_k), (block_q, block_k) = lens, blocks
    want = _brute_force_live(s_q, s_k, block_q, block_k, window)
    np.testing.assert_array_equal(
        fa._live_blocks(True, window, s_q, s_k, block_q, block_k), want)
    for resident, heads in (("q", 1), ("k", 1), ("k", 3)):
        sched = fa._schedule(True, window, s_q, s_k, block_q, block_k,
                             resident=resident, heads=heads)
        assert all(t.dtype == np.int32 for t in sched.tables)
        assert not sched.direct
        assert (sched.blocks, sched.steps) == (1, sched.flags.size)
        steps = iter([(j, t) for j in range(sched.blocks)
                      for t in range(sched.steps * heads)])
        for r, row in enumerate(want if resident == "q" else want.T):
            live = np.flatnonzero(row).tolist()
            partners = [(c, h) for c in live or [0] for h in range(heads)]
            for n, (c, h) in enumerate(partners):
                j, t = next(steps)
                word = int(sched.flags[j * sched.steps + sched.step(t)])
                assert sched.resident_block(j, t, sched.resident) == r
                assert sched.streamed_block(t, sched.streamed) == c
                assert sched.head(t) == h
                assert bool(word & fa._LIVE) == bool(live)
                # first / last on the block's entries: the kernels narrow
                # them to a group's first / last head
                assert bool(word & fa._FIRST) == (n < heads)
                assert bool(word & fa._LAST) == (n >= len(partners) - heads)
        assert next(steps, None) is None


@pytest.mark.parametrize("causal,lens,grid", [
    (False, (384, 512, 128, 256), (3, 2)),  # BERT's path, ring attention's
    (True, (128, 512, 128, 128), (1, 4)),   # the newest queries of a prefix
    (True, (384, 512, 128, 256), (1, 5)),   # rows of 1, 2, 2 live blocks
])
def test_a_schedule_with_no_dead_block_is_walked_directly(causal, lens, grid):
    """Where every block is live (not causal: the whole rectangle,
    row-major) the grid's axes are the resident and the streamed blocks
    themselves and no table is read; where one is dead the live ones lie end
    to end on the inner axis."""
    from analytics_zoo_tpu.ops import flash_attention as fa

    sched = fa._schedule(causal, None, *lens)
    assert (sched.blocks, sched.steps) == grid
    assert sched.direct == (lens[:2] != (384, 512) or not causal)
    if sched.direct:
        assert [(sched.resident_block(j, t, None),
                 sched.streamed_block(t, None))
                for j in range(grid[0]) for t in range(grid[1])] == [
            (j, t) for j in range(grid[0]) for t in range(grid[1])]
        assert all(t.size == 1 for t in sched.tables)


def _pallas_calls(jaxpr, found=None):
    """{kernel name: [grid, ...]} of every `pallas_call` in a jaxpr."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.setdefault(eqn.params["name"], []).append(
                tuple(eqn.params["grid_mapping"].grid))
        for inner in inner_jaxprs(eqn):
            _pallas_calls(inner, found)
    return found


def _traced_grad(shape, kv_heads, window, monkeypatch, causal=True,
                 v_width=None, **blocks):
    """The jaxpr of the kernels' forward and backward at ``shape`` (traced
    from shapes: nothing runs); values ``v_width`` wide if given."""
    monkeypatch.delenv("AZOO_FLASH_BLOCK_Q", raising=False)
    monkeypatch.delenv("AZOO_FLASH_BLOCK_K", raising=False)
    b, n, s, d = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, kv_heads, s, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, kv_heads, s, v_width or d), jnp.bfloat16)

    def loss(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=causal,
                                       window=window,
                                       **blocks).astype(jnp.float32))

    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr


_KANANA = ((1, 32, 16384, 192), 32, True, None, (1, 528), 128)


@pytest.mark.parametrize("shape,kv_heads,causal,window,walk,v_width", [
    ((1, 32, 32768, 64), 8, True, None, (1, 2080), None),  # lfm2 fit-seq32k
    ((2, 32, 8192, 128), 4, True, None, (1, 136), None),   # trinity fit-seq8k
    ((2, 32, 8192, 128), 4, True, 2048, (1, 70), None),    # its window layers
    _KANANA,                                               # kanana fit-seq16k
    ((8, 12, 2048, 64), 12, False, None, (4, 4), None),    # BERT-base widths
])
def test_the_kernels_grids_hold_the_live_blocks_only(shape, kv_heads, causal,
                                                     window, walk, v_width,
                                                     monkeypatch):
    """At the decoder cells' shapes a grid is rows x the live 512 x 512
    blocks of a head on one axis: a dead block is no grid step. The backward
    is one kernel, still named ``zoo_flash_dkv``, whose rows are the query
    heads and which walks the same live blocks: no ``zoo_flash_dq``. Not
    causal the grid is the rectangle it was."""
    b, n = shape[:2]
    blocks, steps = walk
    grids = _pallas_calls(_traced_grad(shape, kv_heads, window, monkeypatch,
                                       causal=causal, v_width=v_width))
    assert grids == {
        "zoo_flash_fwd": [(b * n, blocks, steps)],
        "zoo_flash_dkv": [(b * n, blocks, steps)]}


@pytest.mark.parametrize("shape,kv_heads,causal,window,walk,v_width", [
    ((1, 32, 32768, 64), 8, True, None, (1, 2080), None),
    _KANANA,
])
def test_over_the_vmem_budget_the_backward_is_two_kernels(
        shape, kv_heads, causal, window, walk, v_width, monkeypatch):
    """A row whose dq slab does not fit ``_ONE_KERNEL_VMEM`` keeps the two
    kernels and their grids: dq's rows the query heads, dk/dv's the
    key-value heads, each step once for every query head of the group."""
    from analytics_zoo_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_ONE_KERNEL_VMEM", 0)
    b, n = shape[:2]
    blocks, steps = walk
    grids = _pallas_calls(_traced_grad(shape, kv_heads, window, monkeypatch,
                                       causal=causal, v_width=v_width))
    assert grids == {
        "zoo_flash_fwd": [(b * n, blocks, steps)],
        "zoo_flash_dq": [(b * n, blocks, steps)],
        "zoo_flash_dkv": [(b * kv_heads, blocks, n // kv_heads * steps)]}


def test_the_dq_slab_of_the_cells_fits_the_budget():
    """The slab and its output's two buffers, lane-padded: 33.6 MB at
    kanana's and LFM2's rows, under the 96 MiB budget; some 98 k queries at
    width 128 are not."""
    from analytics_zoo_tpu.ops import flash_attention as fa

    assert fa._dq_slab_bytes(16384, 192, jnp.bfloat16) == 16384 * 256 * 8
    assert fa._dq_slab_bytes(32768, 64, jnp.bfloat16) == 32768 * 128 * 8
    assert fa._dq_slab_bytes(8192, 128, jnp.float32) == 8192 * 128 * 12
    assert fa._dq_slab_bytes(98304, 128, jnp.bfloat16) <= fa._ONE_KERNEL_VMEM
    assert fa._dq_slab_bytes(98816, 128, jnp.bfloat16) > fa._ONE_KERNEL_VMEM


def test_the_backward_builds_are_counted_by_kernels(monkeypatch):
    """``zoo_flash_backward_built_total{kernels}``: one build a trace of the
    backward, under the path it took."""
    from analytics_zoo_tpu.common.observability import flash_backward_built
    from analytics_zoo_tpu.ops import flash_attention as fa

    def built():
        fam = flash_backward_built()
        return {k: fam.labels(kernels=k).value for k in ("one", "two")}

    shape = (1, 2, 1024, 64)
    before = built()
    _traced_grad(shape, 2, None, monkeypatch)
    assert built() == {"one": before["one"] + 1, "two": before["two"]}
    monkeypatch.setattr(fa, "_ONE_KERNEL_VMEM", 0)
    _traced_grad(shape, 2, None, monkeypatch)
    assert built() == {"one": before["one"] + 1, "two": before["two"] + 1}


@pytest.mark.parametrize("s,causal,fits", [
    (32768, True, True),      # 32 896 steps: blocks pinned to 128 at LFM2's
    (65536, True, False),     # 131 328
    (65536, False, True),     # not causal: walked directly, no table
])
def test_a_schedule_too_long_for_smem_is_refused(s, causal, fits):
    """The schedule's three int32 tables are prefetched into SMEM, 1 MiB on
    a v5e: a shape whose tables would not fit there is outside the kernels'
    support (larger blocks bring it back)."""
    q = jax.ShapeDtypeStruct((1, 1, s, 64), jnp.bfloat16)

    def build():
        return jax.eval_shape(lambda q_: flash_attention(
            q_, q_, q_, causal=causal, block_q=128, block_k=128), q)

    if fits:
        assert build().shape == q.shape
    else:
        with pytest.raises(NotImplementedError, match="SMEM"):
            build()


@pytest.mark.parametrize("s_q,s_k,window", [
    (384, 640, None),
    (640, 384, None),   # query blocks before the first key: steps not live
    (384, 640, 130),    # key blocks older than every window: the same, dk/dv
])
def test_flash_cross_lengths_causal_multiblock(s_q, s_k, window):
    # several blocks on BOTH axes with s_q != s_k: exercises the schedule
    # end-to-end through fwd and both backward kernels
    q, k, v = _qkv(jax.random.PRNGKey(7), s_q=s_q, s_k=s_k)
    _check_fwd_and_grads(q, k, v, None, causal=True, window=window)


def test_flash_bias_causal_grad():
    # padding-mask bias UNDER the causal mask: the bias BlockSpec streams
    # through the same scheduled index maps as K/V in all three kernels
    q, k, v = _qkv(jax.random.PRNGKey(8), s_q=256, s_k=384)
    bias = jnp.where(
        jax.random.bernoulli(jax.random.PRNGKey(9), 0.8, (B, N, 1, 384)),
        0.0, -1e9).astype(jnp.float32)
    _check_fwd_and_grads(q, k, v, bias, causal=True)


def _two_kernel_builds():
    from analytics_zoo_tpu.common.observability import flash_backward_built

    return flash_backward_built().labels(kernels="two").value


@pytest.mark.parametrize("case", ["causal", "window", "bias", "bias_causal",
                                  "g_lse"])
def test_the_two_kernel_backward_against_the_reference(case, monkeypatch):
    """With the VMEM budget at nought every backward runs dq's kernel and
    dk/dv's: the fallback keeps the coverage the one kernel has."""
    from analytics_zoo_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_ONE_KERNEL_VMEM", 0)
    before = _two_kernel_builds()
    if case == "causal":
        _check_fwd_and_grads(*_qkv(jax.random.PRNGKey(0)), None, causal=True)
    elif case == "window":
        q, k, v = _qkv(jax.random.PRNGKey(7), s_q=384, s_k=640)
        _check_fwd_and_grads(q, k, v, None, causal=True, window=130)
    elif case == "bias":
        _check_fwd_and_grads(*_qkv(jax.random.PRNGKey(1)),
                             _padding_bias(jax.random.PRNGKey(2)),
                             causal=False)
    elif case == "bias_causal":
        q, k, v = _qkv(jax.random.PRNGKey(8), s_q=256, s_k=384)
        bias = jnp.where(
            jax.random.bernoulli(jax.random.PRNGKey(9), 0.8, (B, N, 1, 384)),
            0.0, -1e9).astype(jnp.float32)
        _check_fwd_and_grads(q, k, v, bias, causal=True)
    else:
        q, k, v = _qkv(jax.random.PRNGKey(0))
        got = _out_lse_and_grads(
            lambda *a: fa.flash_attention_with_lse(*a, causal=True), q, k, v)
        want = _out_lse_and_grads(
            lambda *a: _reference_with_lse(*a, True), q, k, v)
        for a, b, what in zip(got, want, "out lse dq dk dv".split()):
            np.testing.assert_allclose(a, b, err_msg=what, **TOL)
    assert _two_kernel_builds() > before


@pytest.mark.parametrize("kv_heads,window", [(N, None), (N, 130), (1, None)])
def test_the_one_and_the_two_kernel_backward_agree(kv_heads, window,
                                                   monkeypatch):
    """dq sums over the same key blocks in the same order either way: equal
    to the last bit. dk / dv too where no group sums; where one does, the
    one kernel's f32 partials are summed by XLA, within f32 rounding."""
    from analytics_zoo_tpu.ops import flash_attention as fa

    q, k, v = _qkv(jax.random.PRNGKey(21), s_q=384, s_k=640)
    k, v = k[:, :kv_heads], v[:, :kv_heads]
    g = jax.random.normal(jax.random.PRNGKey(22), q.shape, jnp.float32)

    def grads():
        return jax.grad(lambda *a: jnp.vdot(flash_attention(
            *a, causal=True, window=window), g), argnums=(0, 1, 2))(q, k, v)

    one = grads()
    monkeypatch.setattr(fa, "_ONE_KERNEL_VMEM", 0)
    two = grads()
    np.testing.assert_array_equal(np.asarray(one[0]), np.asarray(two[0]))
    for a, b, what in zip(one[1:], two[1:], ("dk", "dv")):
        if kv_heads == N:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6,
                                       err_msg=what)


def test_the_one_kernel_backward_at_kanana_widths():
    """Queries and keys 192 wide beside values 128 wide, 512 x 512 blocks
    (a two-by-two causal walk): the dq slab is 192 wide."""
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(23), 4)
    q = jax.random.normal(kq, (1, 4, 1024, 192), jnp.float32)
    k = jax.random.normal(kk, (1, 4, 1024, 192), jnp.float32)
    v = jax.random.normal(kv, (1, 4, 1024, 128), jnp.float32)
    g = jax.random.normal(kg, (1, 4, 1024, 128), jnp.float32)
    scale = 192 ** -0.5

    def loss(attn):
        return lambda *a: jnp.vdot(attn(*a), g)

    got = jax.value_and_grad(loss(lambda *a: flash_attention(
        *a, causal=True, scale=scale)), argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(loss(lambda *a: _reference_attention(
        *a, None, True, scale)), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for a, b, what in zip(got[1], want[1], ("dq", "dk", "dv")):
        np.testing.assert_allclose(a, b, err_msg=what, **TOL)

#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user calls,
at the published widths of BERT-base (12 blocks, hidden 768, 12 heads,
intermediate 3072, vocab 30522; seq 128, batch 64, bf16 compute, random
weights from a seed):

  1. devices   ``zoo.init_nncontext()``; the platform must be ``tpu`` and the
               ``device_kind`` a key of the peaks table.
  2. train     ``Estimator.train`` over a device-cached set (fused epochs in
               one dispatch) and over a host-fed set (per-step infeed, native
               prefetcher): finite losses, a changed parameter, state on the
               device, no compile on a second call of the same shape; the
               same window timed to ``block_until_ready`` and to a host fetch.
  3. kernels   the Pallas flash-attention kernels, forward and ``jax.grad``,
               compiled by Mosaic and held to ``_reference_attention``; the
               ring-attention per-shard engine on a one-device ``seq`` mesh.
  4. serve     the same BERT through ``InferenceModel`` → ``ServingEngine``
               (bucket-ladder warm-up) → ``serving.http.serve`` → eight HTTP
               predicts, held to a direct jitted forward; one AOT-cache
               store → load round trip into a second ``InferenceModel``.
  5. profile   ``Estimator.set_profile`` over two host-fed steps; the trace
               must hold TPU device ops.
  6. several_chips (only with >= 4 devices) ZeRO-1 + row-sharded cache on a
               ``data=4`` mesh held to the one-chip loss, the TP step on
               ``(2, 2)``, ring attention over ``seq=4``.

Exit code 0 only if every phase ran on a TPU and passed. With no accelerator
(``JAX_PLATFORMS=cpu``, or no chip) the default invocation fails in phase 1,
names the platform it found on stderr, and prints no result. Stdout is two
JSON lines. The last is the verdict, exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``
with the device as JAX reports it. The line before it is the report: the
verdict's keys plus JAX version, per phase pass/fail with compile seconds
apart from run seconds, persistent-compile-cache hits and misses, and
``"claim": null``.

``--rehearse`` is the explicit CPU rehearsal for debugging this script: tiny
widths, kernels in Pallas interpret mode, whatever platform JAX has. Its
summary says ``"rehearsal": true`` and names the platform it ran on; it is
never a device measurement. Sequence generation (``ContinuousBatcher``) is
not covered (docs/known-issues.md).
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import tempfile
import time
import traceback
import urllib.request

FULL = {
    "bert": dict(n_block=12, hidden_size=768, n_head=12,
                 intermediate_size=3072, vocab=30522, seq_len=128),
    "batch": 64,
    "n_samples": 256,          # 4 steps an epoch
    # (name, batch, heads, seq, head_dim, dtype, causal, padding-mask bias,
    #  through the dispatcher)
    "kernels": [
        ("bf16_s2048_mask_dispatched", 4, 12, 2048, 64, "bfloat16",
         False, True, True),
        ("bf16_s4096_causal", 1, 8, 4096, 64, "bfloat16", True, False, False),
        ("f32_s384_tile128", 2, 4, 384, 64, "float32", False, False, False),
        ("bf16_s1024_d256", 1, 4, 1024, 256, "bfloat16", True, False, False),
    ],
    "ring": (1, 4, 512, 64),   # (batch, heads, seq PER DEVICE, head_dim)
    "request_rows": [1, 3, 8, 2, 17, 32, 5, 11],
}
REHEARSAL = {
    "bert": dict(n_block=2, hidden_size=64, n_head=4, intermediate_size=128,
                 vocab=1000, seq_len=16),
    "batch": 8,
    "n_samples": 32,
    "kernels": [
        ("bf16_s512_mask", 1, 2, 512, 64, "bfloat16", False, True, False),
        ("bf16_s1024_causal", 1, 1, 1024, 64, "bfloat16", True, False, False),
        ("f32_s384_tile128", 1, 2, 384, 64, "float32", False, False, False),
    ],
    "ring": (1, 2, 128, 64),
    "request_rows": [1, 3, 8, 2, 17, 32, 5, 11],
}

# tests/test_flash_attention.py: TOL for f32, the bf16 strategy test's
# bounds for bf16 inputs against the f32 reference
F32_TOL = dict(rtol=2e-3, atol=2e-3)
BF16_OUT_TOL = dict(rtol=2e-2, atol=2e-2)
BF16_GRAD_TOL = dict(rtol=6e-2, atol=6e-2)


class NoAccelerator(RuntimeError):
    """Phase 1 found no TPU: nothing else may run, no result is printed."""


def _log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


class Run:
    """Shared state of one smoke run: sizes, counters, what phases hand on."""

    def __init__(self, rehearse: bool):
        self.rehearse = rehearse
        self.sizes = REHEARSAL if rehearse else FULL
        self.device = None
        self.phases = {}
        self.cache_events = {"hits": 0, "misses": 0}
        self.shared = {}       # model / estimator / datasets handed on

    # -- counters ---------------------------------------------------------

    def install_listeners(self) -> None:
        import jax.monitoring

        from analytics_zoo_tpu.common.observability import get_registry

        get_registry()  # installs the zoo_compile_* listener

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_events["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_events["misses"] += 1

        jax.monitoring.register_event_listener(on_event)

    @staticmethod
    def compiles():
        """``(zoo_compile_total, zoo_compile_seconds_total)``."""
        from analytics_zoo_tpu.common.observability import get_registry

        snap = get_registry().snapshot()
        return (int(snap["zoo_compile_total"][()]),
                float(snap["zoo_compile_seconds_total"][()]))

    # -- phase driver -----------------------------------------------------

    def phase(self, name: str, fn) -> dict:
        _log(f"phase {name} ...")
        n0, s0 = self.compiles()
        h0, m0 = self.cache_events["hits"], self.cache_events["misses"]
        t0 = time.perf_counter()
        rec = {"ok": False}
        try:
            rec.update(fn(self) or {})
            rec["ok"] = True
        except NoAccelerator:
            raise
        except Exception as e:  # noqa: BLE001 — a failed phase fails the run
            traceback.print_exc(file=sys.stderr)
            rec.update(getattr(e, "details", {}))
            rec["error"] = f"{type(e).__name__}: {e}"[:500]
        wall = time.perf_counter() - t0
        n1, s1 = self.compiles()
        rec.update(
            wall_s=round(wall, 3), compile_s=round(s1 - s0, 3),
            run_s=round(wall - (s1 - s0), 3), compiles=n1 - n0,
            cache_hits=self.cache_events["hits"] - h0,
            cache_misses=self.cache_events["misses"] - m0)
        self.phases[name] = rec
        _log(f"phase {name}: {'ok' if rec['ok'] else 'FAILED'} "
             f"({rec['wall_s']} s, {rec['compile_s']} s compiling, "
             f"{rec['compiles']} compiles, cache {rec['cache_hits']} hits / "
             f"{rec['cache_misses']} misses)")
        return rec


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _on_platform(tree, platform: str) -> bool:
    import jax

    return all(d.platform == platform
               for leaf in jax.tree_util.tree_leaves(tree)
               if isinstance(leaf, jax.Array) for d in leaf.devices())


def _held_to_reference(name: str, got, want, tols) -> dict:
    """``got`` / ``want``: (out, dq, dk, dv). Raises past the tolerance of
    each; returns the max |err| of each."""
    import numpy as np

    errs = {}
    for tag, a, b, tol in zip(("out", "dq", "dk", "dv"), got, want, tols):
        a, b = np.asarray(a, np.float32), np.asarray(b)
        _check(np.all(np.isfinite(a)), f"{name} {tag}: non-finite")
        np.testing.assert_allclose(a, b, err_msg=f"{name} {tag}", **tol)
        errs[tag] = round(float(np.abs(a - b).max()), 6)
    return errs


def _bert_data(cfg: dict, n: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    seq = cfg["seq_len"]
    ids = rng.integers(0, cfg["vocab"], (n, seq)).astype(np.int32)
    types = np.zeros((n, seq), np.int32)
    mask = np.ones((n, seq), np.float32)
    mask[:, 3 * seq // 4:] = (rng.random((n, seq - 3 * seq // 4)) < 0.5)
    y = rng.integers(0, 2, n).astype(np.int32)
    return [ids, types, mask], y


def _build_bert(cfg: dict):
    from analytics_zoo_tpu.keras.optimizers import SGD
    from analytics_zoo_tpu.tfpark.bert import BERTClassifierNet

    model = BERTClassifierNet(num_classes=2, hidden_drop=0.0, attn_drop=0.0,
                              **cfg)
    model.compile(optimizer=SGD(lr=0.01, momentum=0.9),
                  loss="sparse_categorical_crossentropy")
    return model


# ---------------------------------------------------------------------------
# 1. devices
# ---------------------------------------------------------------------------

def phase_devices(run: Run) -> dict:
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.common.runtime import device_info, device_peaks

    ctx = zoo.init_nncontext()
    run.device = device_info()
    out = {"mesh": {a: int(s) for a, s in zip(ctx.mesh.axis_names,
                                              ctx.mesh.devices.shape)}}
    if run.rehearse:
        return out
    if run.device["platform"] != "tpu":
        raise NoAccelerator(
            f"JAX found platform {run.device['platform']!r} "
            f"({run.device['count']} x {run.device['kind']}), not a TPU")
    out["peaks"] = device_peaks(run.device["kind"])  # KeyError if unknown
    return out


# ---------------------------------------------------------------------------
# 2. train
# ---------------------------------------------------------------------------

def phase_train(run: Run) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu import native
    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.data.pmem import cached_feature_set
    from analytics_zoo_tpu.engine.triggers import MaxEpoch
    from analytics_zoo_tpu.parallel.sharding import shard_batch

    ctx = zoo.get_nncontext()
    cfg, batch, n = (run.sizes["bert"], run.sizes["batch"],
                     run.sizes["n_samples"])
    model = _build_bert(cfg)
    est = model._get_estimator()
    criterion = model.criterion
    est._ensure_state()
    head = model.head.name
    kernel_before = np.asarray(est.tstate.params[head]["kernel"])
    xs, y = _bert_data(cfg, n, seed=5)
    out = {"native_available": bool(native.available())}

    def _second_call_flat(train_more, what: str) -> None:
        c0, _ = run.compiles()
        train_more()
        c1, _ = run.compiles()
        _check(c1 == c0, f"{what}: zoo_compile_total rose {c0} -> {c1} on a "
                         "second train() of the same shape")

    # -- device-cached: E epochs fused into one dispatch (_make_train_fit)
    dev_fs = ArrayFeatureSet(xs, y).cache_device()
    est.train(dev_fs, criterion, end_trigger=MaxEpoch(2), batch_size=batch)
    _check(np.isfinite(est.run_state.loss),
           f"device-cached loss {est.run_state.loss}")
    _check(any(k[0] == "train_fit" for k in est._jit_cache),
           "device-cached train did not take the fused-fit path")
    out["device_cached_loss"] = float(est.run_state.loss)
    _second_call_flat(
        lambda: est.train(dev_fs, criterion, end_trigger=MaxEpoch(4),
                          batch_size=batch), "device-cached")

    # -- host-fed: per-step infeed through the native prefetcher
    host_fs = cached_feature_set(xs, y, memory_type="DRAM")
    out["host_fed_by"] = type(host_fs).__name__
    est.train(host_fs, criterion, end_trigger=MaxEpoch(5), batch_size=batch)
    _check(np.isfinite(est.run_state.loss),
           f"host-fed loss {est.run_state.loss}")
    out["host_fed_loss"] = float(est.run_state.loss)
    _second_call_flat(
        lambda: est.train(host_fs, criterion, end_trigger=MaxEpoch(6),
                          batch_size=batch), "host-fed")

    kernel_after = np.asarray(est.tstate.params[head]["kernel"])
    _check(not np.array_equal(kernel_before, kernel_after),
           "no parameter changed")
    _check(np.all(np.isfinite(kernel_after)), "non-finite parameter")
    _check(_on_platform(est.tstate, run.device["platform"]),
           f"TrainState leaves not on {run.device['platform']} devices")
    _check(est.run_state.iteration == 6 * (n // batch),
           f"iteration count {est.run_state.iteration}")

    # -- is block_until_ready a barrier? The same window of back-to-back
    # step dispatches, ended once by block_until_ready and once by a host
    # fetch of an updated parameter.
    step_fn = est._jit_cache_get(est._cache_token("train", criterion, None,
                                                  None))
    _check(step_fn is not None, "host-fed step not in the estimator's cache")
    dev_batch = (tuple(shard_batch(ctx.mesh, a[:batch]) for a in xs),
                 shard_batch(ctx.mesh, y[:batch]),
                 shard_batch(ctx.mesh, np.ones(batch, np.float32)))
    key = jax.random.PRNGKey(0)
    steps = 8

    def _window(end) -> float:
        ts = est.tstate
        t0 = time.perf_counter()
        for _ in range(steps):
            ts, _loss = step_fn(ts, dev_batch, key, None)
        end(ts)
        dt = time.perf_counter() - t0
        est.tstate = ts    # the step donates its input state
        return dt

    fetch = lambda ts: float(jnp.sum(ts.params[head]["kernel"]))  # noqa: E731
    c0, _ = run.compiles()
    _window(fetch)                          # warm both window endings
    out["barrier"] = {
        "steps": steps,
        "block_until_ready_s": round(_window(jax.block_until_ready), 4),
        "host_fetch_s": round(_window(fetch), 4),
        "block_until_ready_again_s": round(
            _window(jax.block_until_ready), 4),
    }
    out["barrier"]["compiles_in_windows"] = run.compiles()[0] - c0

    run.shared.update(model=model, est=est, criterion=criterion,
                      host_fs=host_fs, batch=batch)
    return out


# ---------------------------------------------------------------------------
# 3. kernels
# ---------------------------------------------------------------------------

def _kernel_case(run: Run, name, b, n, s, d, dtype, causal, masked,
                 dispatched) -> dict:
    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.ops import attention as att
    from analytics_zoo_tpu.ops.flash_attention import (_interpret,
                                                       flash_attention)

    on_tpu = run.device["platform"] == "tpu"
    dt = jnp.dtype(dtype)
    kq, kk, kv, kg, kl = jax.random.split(jax.random.PRNGKey(len(name)), 5)
    q32, k32, v32 = (jax.random.normal(kx, (b, n, s, d), jnp.float32)
                     for kx in (kq, kk, kv))
    g = jax.random.normal(kg, (b, n, s, d), jnp.float32)
    bias = None
    if masked:  # BERT's padding mask: (B, 1, 1, S), a ragged tail masked out
        lens = jax.random.randint(kl, (b,), 3 * s // 4, s)
        valid = jnp.arange(s)[None, :] < lens[:, None]
        bias = jnp.where(valid, 0.0, -1e9)[:, None, None, :].astype(
            jnp.float32)
    q, k, v = (a.astype(dt) for a in (q32, k32, v32))
    scale = d ** -0.5

    if dispatched:
        _check(att._auto_use_flash(q, k) == on_tpu,
               f"{name}: dispatcher routing {att._auto_use_flash(q, k)} on "
               f"{run.device['platform']}")

        def kernel(q_, k_, v_):
            return att.scaled_dot_product_attention(
                q_, k_, v_, bias=None if bias is None else bias.astype(dt),
                causal=causal, scale=scale,
                use_flash=None if on_tpu else True)
    else:
        def kernel(q_, k_, v_):
            return flash_attention(
                q_, k_, v_, bias=None if bias is None else bias.astype(dt),
                causal=causal, scale=scale)

    def loss_k(q_, k_, v_):
        return jnp.vdot(kernel(q_, k_, v_).astype(jnp.float32), g)

    def loss_r(q_, k_, v_):
        return jnp.vdot(att._reference_attention(q_, k_, v_, bias, causal,
                                                 scale), g)

    fwd = jax.jit(kernel)
    bwd = jax.jit(jax.grad(loss_k, argnums=(0, 1, 2)))
    out = {"shape": [b, n, s, d], "dtype": dtype, "causal": causal,
           "masked": masked, "dispatched": dispatched}
    if on_tpu:
        _check(not _interpret(), "_interpret() is True on a TPU")
        for tag, fn in (("fwd", fwd), ("bwd", bwd)):
            text = fn.lower(q, k, v).as_text()
            _check("tpu_custom_call" in text,
                   f"{name} {tag}: no Mosaic custom call in the lowering")
        out["mosaic"] = True
    else:
        out["mosaic"] = None   # interpret mode: nothing for Mosaic to compile

    o = fwd(q, k, v)
    grads = bwd(q, k, v)
    # the reference sees f32 inputs and exact f32 matmuls, as in the tests
    with jax.default_matmul_precision("highest"):
        o_ref = jax.jit(lambda a, b_, c: att._reference_attention(
            a, b_, c, bias, causal, scale))(q32, k32, v32)
        grads_ref = jax.jit(jax.grad(loss_r, argnums=(0, 1, 2)))(
            q32, k32, v32)
    _check(o.dtype == dt and o.shape == (b, n, s, d),
           f"{name}: output {o.dtype}{o.shape}")
    tols = ([F32_TOL] * 4 if dtype == "float32"
            else [BF16_OUT_TOL] + [BF16_GRAD_TOL] * 3)
    out["max_abs_err"] = _held_to_reference(
        name, (o, *grads), (o_ref, *grads_ref), tols)
    return out


def _ring_case(run: Run, n_dev: int) -> dict:
    """Ring attention over a ``seq`` mesh of ``n_dev`` devices, forward and
    grad, against the reference. On TPU the flash per-shard engine must be
    the one auto-selected."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from analytics_zoo_tpu.ops.attention import _reference_attention
    from analytics_zoo_tpu.parallel import ring_attention as ring

    on_tpu = run.device["platform"] == "tpu"
    b, h, s_local, d = run.sizes["ring"]
    s = s_local * n_dev
    mesh = Mesh(np.asarray(jax.devices()[:n_dev]), ("seq",))
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(17), 4)
    q32, k32, v32 = (jax.random.normal(kx, (b, h, s, d), jnp.float32)
                     for kx in (kq, kk, kv))
    g = jax.random.normal(kg, (b, h, s, d), jnp.float32)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q32, k32, v32))
    if on_tpu:
        _check(ring._flash_ring_supported(q, k, v, mesh, "seq"),
               "flash ring engine not auto-selected on TPU")
    use_flash = None if on_tpu else True

    def loss(q_, k_, v_):
        return jnp.vdot(ring.ring_attention(
            q_, k_, v_, mesh, causal=True,
            use_flash=use_flash).astype(jnp.float32), g)

    def loss_r(q_, k_, v_):
        return jnp.vdot(_reference_attention(q_, k_, v_, None, True,
                                             d ** -0.5), g)

    o = jax.jit(lambda a, b_, c: ring.ring_attention(
        a, b_, c, mesh, causal=True, use_flash=use_flash))(q, k, v)
    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        o_ref = _reference_attention(q32, k32, v32, None, True, d ** -0.5)
        grads_ref = jax.grad(loss_r, argnums=(0, 1, 2))(q32, k32, v32)
    return {"devices": n_dev, "shape": [b, h, s, d],
            "shards_on": sorted(d_.id for d_ in o.devices()),
            "max_abs_err": _held_to_reference(
                "ring", (o, *grads), (o_ref, *grads_ref),
                [BF16_OUT_TOL] + [BF16_GRAD_TOL] * 3)}


def phase_kernels(run: Run) -> dict:
    """Every case runs even when an earlier one fails — one chip run should
    show every kernel the compiler refuses — and any failure fails the
    phase."""
    cases = [(c[0], lambda c=c: _kernel_case(run, *c))
             for c in run.sizes["kernels"]]
    cases.append(("ring_engine_seq1", lambda: _ring_case(run, 1)))
    out, failed = {}, []
    for name, case in cases:
        _log(f"  kernel {name} ...")
        try:
            out[name] = case()
        except Exception as e:  # noqa: BLE001 — reported, then re-raised
            traceback.print_exc(file=sys.stderr)
            out[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
            failed.append(name)
        gc.collect()
    if failed:
        err = AssertionError(f"kernel cases failed: {failed}")
        err.details = out
        raise err
    return out


# ---------------------------------------------------------------------------
# 4. serve
# ---------------------------------------------------------------------------

def _http_predict(port: int, name: str, inputs) -> "list":
    body = json.dumps({"inputs": [a.tolist() for a in inputs]}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/models/{name}:predict", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        _check(resp.status == 200, f"HTTP {resp.status}")
        return json.loads(resp.read())["predictions"]


def phase_serve(run: Run) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.common.observability import aot_cache_counters
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.serving import ServingEngine
    from analytics_zoo_tpu.serving import http as serving_http

    _check("model" in run.shared, "needs the model phase 2 trained")
    model, est = run.shared["model"], run.shared["est"]
    cfg = run.sizes["bert"]
    rows = run.sizes["request_rows"]
    pool, _y = _bert_data(cfg, max(rows), seed=11)

    # the direct jitted forward every served answer is held to (the
    # estimator's own mixed-precision cast, f32 out)
    cast = est._cast_for_compute

    @jax.jit
    def direct(params, state, x):
        y, _ = model.apply(cast(params), state, cast(x), training=False,
                           rng=None)
        return y.astype(jnp.float32)

    want = np.asarray(direct(est.tstate.params, est.tstate.model_state,
                             [jnp.asarray(a) for a in pool]))
    _check(np.all(np.isfinite(want)), "direct forward not finite")

    out = {}
    counters = aot_cache_counters()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_aot_") as aot_dir:
        inf = InferenceModel(aot_cache_dir=aot_dir).do_load_keras(model)
        engine = ServingEngine()
        server = None
        try:
            engine.register("bert", inf,
                            example_input=[a[:1] for a in pool])
            out["aot_stores"] = int(counters["stores"].value)
            _check(out["aot_stores"] >= 1, "warm-up stored no AOT executable")
            server, _thread = serving_http.serve(engine, port=0)
            warm, _ = run.compiles()
            worst = 0.0
            for i, r in enumerate(rows):
                lo = (i * 7) % (len(pool[0]) - r + 1)
                got = np.asarray(_http_predict(
                    server.server_port, "bert",
                    [a[lo:lo + r] for a in pool]), np.float32)
                _check(got.shape == (r, 2), f"request {i}: shape {got.shape}")
                _check(np.all(np.isfinite(got)), f"request {i}: non-finite")
                np.testing.assert_allclose(
                    got, want[lo:lo + r], err_msg=f"request {i} ({r} rows)",
                    **BF16_OUT_TOL)
                worst = max(worst, float(np.abs(got - want[lo:lo + r]).max()))
            after, _ = run.compiles()
            _check(after == warm, f"zoo_compile_total rose {warm} -> {after} "
                                  "while serving after warm-up")
            out.update(requests=len(rows), max_abs_err=round(worst, 6),
                       compiles_while_serving=after - warm)

            # AOT store -> load round trip into a second InferenceModel
            probe = [a[:max(rows)] for a in pool]
            first = inf.do_predict(probe)
            hits0, errs0 = counters["hits"].value, counters["errors"].value
            c0, _ = run.compiles()
            inf2 = InferenceModel(aot_cache_dir=aot_dir).do_load_keras(model)
            second = inf2.do_predict(probe)
            _check(counters["hits"].value == hits0 + 1,
                   "second InferenceModel did not load from the AOT cache")
            _check(counters["errors"].value == errs0, "AOT cache load error")
            _check(run.compiles()[0] == c0,
                   "loading an AOT executable compiled")
            np.testing.assert_array_equal(np.asarray(first),
                                          np.asarray(second))
            out["aot_round_trip"] = "loaded, no compile, equal output"
        finally:
            if server is not None:
                server.shutdown()
                server.server_close()
            engine.shutdown()
    return out


# ---------------------------------------------------------------------------
# 5. profile
# ---------------------------------------------------------------------------

def phase_profile(run: Run) -> dict:
    from analytics_zoo_tpu.common.trace_tools import summarize_trace, top_ops
    from analytics_zoo_tpu.engine.triggers import MaxEpoch

    _check("est" in run.shared, "needs the estimator phase 2 trained")
    est, host_fs = run.shared["est"], run.shared["host_fs"]
    plane = "TPU" if run.device["platform"] == "tpu" else "CPU"
    with tempfile.TemporaryDirectory(prefix="chip_smoke_trace_") as log_dir:
        est.set_profile(log_dir, start_iteration=1, num_iterations=2)
        est.train(host_fs, run.shared["criterion"],
                  end_trigger=MaxEpoch(est.run_state.epoch + 1),
                  batch_size=run.shared["batch"])
        summary = summarize_trace(log_dir)
        ops = (top_ops(log_dir, plane_substr="TPU", n=5) if plane == "TPU"
               else top_ops(log_dir, line="python", plane_substr="CPU", n=5))
        modules = (top_ops(log_dir, line="XLA Modules", plane_substr="TPU")
                   if plane == "TPU" else [])
    # device planes by line name; host planes (one line per thread) by count
    planes = {p: (sorted(v["lines"]) if plane in p and plane != "CPU"
                  else f"{len(v['lines'])} lines")
              for p, v in summary.items()}
    _log(f"  trace planes/lines: {json.dumps(planes)}")
    _check(any(plane in p for p in planes), f"no {plane} plane in the trace")
    _check(len(ops) > 0, f"top_ops found no {plane} device ops")
    if plane == "TPU":  # the window must hold the two steps whole, per chip
        steps = [c for name, _ms, c in modules if "train_step" in name]
        _check(steps == [2 * run.device["count"]],
               f"traced train_step modules: {modules}")
    return {"planes": planes,
            "modules": [[name[:60], round(ms, 3), c]
                        for name, ms, c in modules],
            "top_ops": [[name[:60], round(ms, 3), count]
                        for name, ms, count in ops]}


# ---------------------------------------------------------------------------
# 6. several chips
# ---------------------------------------------------------------------------

def phase_several_chips(run: Run) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.common import nncontext
    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.engine.estimator import Estimator
    from analytics_zoo_tpu.engine.triggers import MaxEpoch
    from analytics_zoo_tpu.keras.optimizers import SGD
    from analytics_zoo_tpu.parallel.sharding import shard_batch

    import __graft_entry__ as graft

    n_dev = 4
    devices = jax.devices()[:n_dev]
    out = {"device_ids": [d.id for d in devices],
           "device_coords": [list(getattr(d, "coords", ())) for d in devices]}

    def _spread(arr, what: str, rows_each=None) -> list:
        shards = arr.addressable_shards
        devs = {s.device.id for s in shards}
        _check(len(devs) == n_dev,
               f"{what}: shards on devices {sorted(devs)}, want {n_dev}")
        _check(len({str(s.index) for s in shards}) == n_dev,
               f"{what}: shards are replicas, not {n_dev} distinct slices")
        if rows_each is not None:
            _check(all(s.data.shape[0] == rows_each for s in shards),
                   f"{what}: {[s.data.shape for s in shards]}")
        return sorted(devs)

    # -- data=4: ZeRO-1 moments + row-sharded device cache
    run.shared.clear()
    gc.collect()
    nncontext.stop_nncontext()
    ctx = nncontext.init_nncontext(mesh_shape=(n_dev, 1))
    cfg, batch, n = (run.sizes["bert"], run.sizes["batch"],
                     run.sizes["n_samples"])
    xs, y = _bert_data(cfg, n, seed=5)
    first = [a[:batch] for a in xs], y[:batch]

    def _dp_vs_one_chip(exact: bool):
        """One global batch, one step: the step's loss is the loss at the
        initial parameters over all `batch` rows, which one chip can
        compute from the same parameters. ``exact`` runs both sides in f32
        at HIGHEST matmul precision, where the only difference left is the
        sharding; the bf16 difference also carries rounding that depends on
        the per-device batch, and is reported, not judged."""
        model = _build_bert(cfg)
        if exact:
            model.compute_dtype = None
        est = Estimator(model, SGD(lr=0.01, momentum=0.9), zero1=True)
        est._ensure_state()
        params0 = jax.device_get(est.tstate.params)
        state0 = jax.device_get(est.tstate.model_state)
        one = ArrayFeatureSet(*first).cache_device(shard_rows=True)
        cast = est._cast_for_compute

        @jax.jit
        def one_chip_loss(params, state, x, y_):
            pred, _ = model.apply(cast(params), state, cast(x),
                                  training=True, rng=jax.random.PRNGKey(0))
            return model.criterion(y_, pred.astype(jnp.float32))

        with (jax.default_matmul_precision("highest") if exact
              else contextlib.nullcontext()):
            est.train(one, model.criterion, end_trigger=MaxEpoch(1),
                      batch_size=batch)
            ref = float(one_chip_loss(*jax.device_put(
                (params0, state0, *first), devices[0])))
        dp = float(est.run_state.loss)
        return {"dp_loss": dp, "one_chip_loss": ref,
                "abs_diff": abs(dp - ref)}, est, one, model

    out["parity_f32_highest"], est, one, model = _dp_vs_one_chip(exact=True)
    _check(out["parity_f32_highest"]["abs_diff"] <= 1e-5,
           f"DP vs one-chip loss: {out['parity_f32_highest']}")
    del est, one, model
    gc.collect()
    out["parity_bf16"], est, one, model = _dp_vs_one_chip(exact=False)
    _check(np.isfinite(out["parity_bf16"]["dp_loss"]), "bf16 DP loss")
    out["cache_rows_on"] = _spread(one._dev_xs[0], "cache rows",
                                   rows_each=batch // n_dev)

    # the fused-epoch path over the row-sharded cache
    fs = ArrayFeatureSet(xs, y).cache_device(shard_rows=True)
    est.train(fs, model.criterion, end_trigger=MaxEpoch(3), batch_size=batch)
    _check(np.isfinite(est.run_state.loss), f"loss {est.run_state.loss}")
    _check(any(k[0] == "train_fit" for k in est._jit_cache),
           "row-sharded train did not take the fused-fit path")
    moments = [l for l in jax.tree_util.tree_leaves(est.tstate.opt_state)
               if isinstance(l, jax.Array)
               and "data" in str(getattr(l.sharding, "spec", ""))]
    _check(len(moments) > 0, "no ZeRO-1 moment sharded over the data axis")
    out["zero1_moments_on"] = _spread(
        max(moments, key=lambda l: l.size), "ZeRO-1 moment")
    out["zero1_sharded_leaves"] = len(moments)
    out["batch_shards_on"] = _spread(
        shard_batch(ctx.mesh, xs[0][:batch]), "batch", batch // n_dev)
    out["fused_loss"] = float(est.run_state.loss)
    del est, fs, one, model
    gc.collect()

    # -- (2, 2): the TP-annotated step of the dry run, on real chips
    tp_model, tp_est, mesh_shape, _x, _y = graft.tp_step(n_dev)
    kernel = tp_est.tstate.params[tp_model.layers()[0].name]["kernel"]
    tp_devs = {s.device.id for s in kernel.addressable_shards}
    _check(len(tp_devs) == n_dev, f"TP kernel on devices {sorted(tp_devs)}")
    _check(len({str(s.index) for s in kernel.addressable_shards}) == 2,
           "col-parallel kernel is not split two ways over the model axis")
    tp_ctx = nncontext.get_nncontext()
    out["tp"] = {"mesh_shape": list(mesh_shape),
                 "mesh_device_ids": np.vectorize(lambda d: d.id)(
                     tp_ctx.mesh.devices).tolist(),
                 "loss": float(tp_est.run_state.loss)}

    # -- seq=4: ring attention, flash engine auto-selected
    out["ring"] = _ring_case(run, n_dev)
    _check(len(out["ring"]["shards_on"]) == n_dev,
           f"ring output on devices {out['ring']['shards_on']}")
    nncontext.stop_nncontext()
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse", action="store_true",
        help="explicit CPU rehearsal of this script: tiny widths, kernels "
             "in interpret mode, any platform; never a device measurement")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    run = Run(rehearse=args.rehearse)
    run.install_listeners()   # imports the package: the compile cache is placed
    t0 = time.perf_counter()
    try:
        run.phase("devices", phase_devices)
    except NoAccelerator as e:
        _log(f"no accelerator: {e}")
        return 1
    if not run.phases["devices"]["ok"]:
        _log("phase devices failed; nothing else can run")
        return 1
    if run.rehearse:
        _log(f"REHEARSAL — platform: {run.device['platform']} "
             "(not a device measurement)")
    phases = [("train", phase_train), ("kernels", phase_kernels),
              ("serve", phase_serve), ("profile", phase_profile)]
    if run.device["count"] >= 4:
        phases.append(("several_chips", phase_several_chips))
    for name, fn in phases:
        run.phase(name, fn)
    close = getattr(run.shared.get("host_fs"), "close", None)
    if close is not None:
        close()   # joins the native prefetcher's threads

    ok = all(p["ok"] for p in run.phases.values())
    verdict = {"ok": ok, "device": run.device}
    report = {
        **verdict,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "rehearsal": run.rehearse,
        "wall_s": round(time.perf_counter() - t0, 1),
        "compile_cache": {"dir": jax.config.jax_compilation_cache_dir,
                          **run.cache_events},
        "phases": run.phases,
        "claim": None,
    }
    # two stdout lines: the report, then the verdict in exactly the shape
    # the accelerator check reads — {"ok", "device": {platform, kind, count}}
    print(json.dumps(report), flush=True)
    print(json.dumps(verdict), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

"""Every operation of a decoder's train step under one named scope: the nine
that the benchmark's readers and drivers name, the eight beside them, and the
Mamba-2 mixer's three, over the four decoder families at the tiny sizes of
their own tests, in bfloat16 with the blocks rematerialised, through the
estimator's own step on the CPU. A scope is metadata: the compiled program is
the same without the eleven. And the SwiGLU families' steps compile to what
they compiled to before the expert path took its activation as data."""

import contextlib
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from test_causal_lm import CFG as AFMOE
from test_kanana2 import CFG as DEEPSEEK_V3
from test_lfm2 import CFG as LFM2_MOE
from test_nemotronh import CFG as NEMOTRON_H

ACCEPTED = ("attn.window", "attn.full", "attn.latent", "conv.short",
            "moe.route", "moe.experts", "moe.shared", "lm.loss", "optimizer")
SSM = ("ssm.proj_in", "ssm.scan", "ssm.proj_out")
NEW = ("attn.proj_in", "attn.qk_rotary", "attn.proj_out", "block.norm",
       "block.cast", "mlp.dense", "lm.embed", "lm.head") + SSM + (
           "moe.balance",)
SCOPES = ACCEPTED + NEW
FAMILIES = {"afmoe": AFMOE, "lfm2_moe": LFM2_MOE, "deepseek_v3": DEEPSEEK_V3,
            "nemotron_h": NEMOTRON_H}
# `benchmark.scope_shares.program_text` of each SwiGLU family's compiled step
# as it stood before `held_experts_ffn` took the expert's activation as data;
# `afmoe`'s since its step also returns the (query, key) pairs inside its
# windows (`CausalLM.train_stats`), one scalar more and nothing else
SWIGLU_STEPS = {
    "afmoe": "f84a5cddb2d89119f1ec84a875cf1744653c56fbb50ffcb2a63373a479e8205d",
    "deepseek_v3":
        "eb87b296fd436b0711a161896589c0433c411efcb8aa49522ad7c6823ab2c2d1",
    "lfm2_moe":
        "a368380daf70371d5e8416eb915d0df838688e60449b37492480b30197fc959a"}
# the instructions a device's time goes to besides elementwise passes
WORK = ("dot", "gather", "scatter", "convolution", "custom-call")
NAMED_SCOPE = jax.named_scope


def _lowered(cfg):
    from analytics_zoo_tpu.common import nncontext
    from analytics_zoo_tpu.keras.engine.base import reset_name_counts
    from analytics_zoo_tpu.keras.optimizers import Adam
    from benchmark import models_lm

    reset_name_counts()           # the same parameter names, the same order
    nncontext.init_nncontext(mesh_shape=(1, 8))
    model = models_lm._build(dict(cfg, compute_dtype="bfloat16"))
    model.compile(optimizer=Adam(lr=1e-3),
                  loss="token_crossentropy_from_logits")
    est = model._get_estimator()
    est._ensure_state()
    ids = jax.ShapeDtypeStruct((2, cfg["seq_len"]), jnp.int32)
    batch = (ids, ids, jax.ShapeDtypeStruct((2,), jnp.float32))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.jit(est._train_step_body(model.criterion)).lower(
        est.tstate, batch, key)


def _accepted_only(name):
    return contextlib.nullcontext() if name in NEW else NAMED_SCOPE(name)


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def step(request):
    """The family's step: its un-optimized HLO with source information, its
    compiled text, the compiled text of the same step built with the
    eleven scopes outside the benchmark's nine taken out, and the family."""
    lowered = _lowered(FAMILIES[request.param])
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "named_scope", _accepted_only)
        bare = _lowered(FAMILIES[request.param]).compile().as_text()
    return (lowered.as_text("hlo", debug_info=True),
            lowered.compile().as_text(), bare, request.param)


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEES = re.compile(r"(to_apply|condition|body|true_computation|"
                      r"false_computation|calls)=%?([\w.\-]+)|"
                      r"branch_computations=\{([^}]*)\}")


def _full_names(hlo: str):
    """(opcode, op_name as XLA writes it once it has inlined every call) of
    each instruction, down every call path from the entry. A called
    function's names are relative to its call (`jit(_where)`); a region's (a
    loop's body, a branch, a reduction's adder) to the function around it."""
    comps, entry, current = {}, None, None
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head and "=" not in line.split("{")[0]:
            current = comps.setdefault(head.group(1), [])
            if line.startswith("ENTRY"):
                entry = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m and current is not None:
            op = _OPCODE.search(m.group(2))
            name = _OP_NAME.search(line)
            callees = []
            for kind, one, many in _CALLEES.findall(line):
                callees += ([(kind, one)] if one else
                            [("branch", c.strip().lstrip("%"))
                             for c in many.split(",")])
            current.append((op.group(1) if op else "",
                            name.group(1) if name else "", callees))
    out, seen = [], set()

    def visit(comp, prefix):
        if (comp, prefix) in seen:
            return
        seen.add((comp, prefix))
        for opcode, name, callees in comps[comp]:
            full = "/".join(p for p in (prefix, name) if p)
            out.append((opcode, full))
            for kind, callee in callees:
                visit(callee, full if opcode == "call" and kind == "to_apply"
                      else prefix)

    visit(entry, "")
    return out


def _named(full: str):
    return [s for s in SCOPES if s in full]


def test_no_scope_name_holds_another_and_the_benchmark_names_the_nine():
    from benchmark import (fit_kanana2, fit_lfm2, fit_mellum2, fit_nemotronh,
                           trace_lm)

    assert len(set(SCOPES)) == 21
    for a in SCOPES:
        assert [b for b in SCOPES if a in b] == [a], a
    assert set(trace_lm.SCOPES + fit_lfm2.SCOPES + fit_kanana2.SCOPES) == set(
        ACCEPTED)
    assert fit_nemotronh.SCOPES == SSM
    assert fit_mellum2.SCOPES == ("moe.balance",)


def test_every_product_look_up_and_kernel_is_under_one_scope(step):
    names = _full_names(step[0])
    work = [(op, full) for op, full in names if op in WORK]
    assert len(work) > 20 and any(op == "dot" for op, _ in work)
    loose = [(op, full) for op, full in work if len(_named(full)) != 1]
    assert not loose, loose[:10]


def test_no_op_name_names_two_scopes(step):
    names = _full_names(step[0])
    both = {full for _, full in names if len(_named(full)) > 1}
    assert not both, sorted(both)[:10]
    # and every one of the eleven is in the step of some family (the dense
    # feed-forward, rotary and the input projection are in the first three;
    # the Mamba mixer's three scopes in nemotron_h's, which has no dense
    # layer)
    found = {s for _, full in names for s in _named(full)}
    assert {"block.norm", "block.cast", "lm.embed", "lm.head",
            "attn.proj_out"} <= found
    assert ("mlp.dense" in found) == (step[3] != "nemotron_h")
    assert set(SSM) & found == (set(SSM) if step[3] == "nemotron_h"
                                else set())


def test_the_scopes_are_metadata_only(step):
    from benchmark.scope_shares import program_text

    _, compiled, bare, _ = step
    assert compiled != bare                 # the eleven are in its metadata
    assert program_text(compiled) == program_text(bare)


@pytest.mark.parametrize("family", sorted(SWIGLU_STEPS))
def test_the_swiglu_steps_compile_as_before_the_squared_relu_experts(family):
    from benchmark.scope_shares import program_text

    text = program_text(_lowered(FAMILIES[family]).compile().as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == SWIGLU_STEPS[family]


# Where the fused q / k norm and rotary kernels (``ops/qk_rotary.py``) run:
# on the chip at head widths 64 and 128; here in interpret mode, with the
# dispatcher told it is on the chip, at a width and a length they take.
KERNEL_SHAPES = {
    "afmoe": dict(head_dim=64, seq_len=512),
    "lfm2_moe": dict(hidden_size=128, num_attention_heads=2,
                     num_key_value_heads=2, seq_len=512),
}


@pytest.mark.parametrize("family", sorted(KERNEL_SHAPES))
def test_the_fused_q_k_kernels_run_under_the_rotary_scope(family,
                                                          monkeypatch):
    from analytics_zoo_tpu.ops import qk_rotary

    monkeypatch.setattr(qk_rotary, "_on_chip", lambda: True)
    lowered = _lowered(dict(FAMILIES[family], **KERNEL_SHAPES[family]))
    names = [full for _, full in _full_names(
        lowered.as_text("hlo", debug_info=True)) if "zoo_qk_rotary" in full]
    for kernel in ("zoo_qk_rotary_fwd", "zoo_qk_rotary_bwd"):
        assert any(kernel in full for full in names), kernel
    outside = [full for full in names if _named(full) != ["attn.qk_rotary"]]
    assert not outside, outside[:10]


# Where the chunked scan's kernels (``ops/ssd.py``) run: on the chip at head
# widths that divide a lane tile, B and C a multiple of 128 wide; here in
# interpret mode, with the dispatcher told it is on the chip.
SSD_SHAPE = dict(mamba_head_dim=64, ssm_state_size=128, chunk_size=64,
                 seq_len=128)


def test_the_scan_kernels_run_under_the_scan_scope(monkeypatch):
    """Forward, the rematerialised forward and the backward: every
    ``zoo_ssd_fwd`` / ``zoo_ssd_bwd`` of the nemotron_h step sits under
    ``ssm.scan`` alone."""
    from analytics_zoo_tpu.ops import ssd

    monkeypatch.setattr(ssd, "_on_chip", lambda: True)
    lowered = _lowered(dict(NEMOTRON_H, **SSD_SHAPE))
    names = [full for _, full in _full_names(
        lowered.as_text("hlo", debug_info=True)) if "zoo_ssd" in full]
    for kernel in ("zoo_ssd_fwd", "zoo_ssd_bwd"):
        assert any(kernel in full for full in names), kernel
    assert any("rematted_computation" in full and "zoo_ssd_fwd" in full
               for full in names)
    outside = [full for full in names if _named(full) != ["ssm.scan"]]
    assert not outside, outside[:10]

"""`scope_shares.py`, the builder's breakdown of a decoder step by every
named scope (PR 39): the reduction on a written-out trace and step text, and
a run of the tiny language-model cell on the CPU, whose trace holds no device
plane, so that every number of the breakdown is null there."""

import json

import pytest

from benchmark import scope_shares, trace_lm
from benchmark.tests import tiny, tiny_lm

SEED = 2 ** 31 + 39

HLO = """\
  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(train_step)/jvp()/block.norm/mul" stack_frame_id=3}
  %fusion.2 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(train_step)/transpose(jvp())/checkpoint/rematted_computation/attn.proj_in/dot_general"}
  %copy.3 = bf16[8]{0} copy(%p), metadata={op_name="jit(train_step)/jvp()/reshape"}
  ROOT %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(train_step)/optimizer/mul"}
"""


def _event(line_no: int, start_ms: float, end_ms: float):
    text = HLO.splitlines()[line_no].split(", metadata")[0].strip()
    return (text.replace("ROOT ", ""), int(start_ms * 1e6), int(end_ms * 1e6))


def test_a_step_by_scope_with_its_rematerialised_part_and_what_is_under_none():
    devices = {"/device:TPU:0": {
        "modules": [("jit_train_step(7)", 0, int(20e6)),
                    ("jit_bench_clock_mark(1)", int(30e6), int(31e6))],
        "ops": [_event(0, 0, 2), _event(1, 2, 5), _event(2, 5, 6),
                _event(3, 6, 10), _event(0, 10, 12), _event(1, 12, 15),
                _event(2, 15, 16), _event(3, 16, 18)]}}
    seen, kept = {}, trace_lm.SCOPES
    with scope_shares.reading(seen):
        assert set(scope_shares.SCOPES) <= set(trace_lm.SCOPES)
        scopes = trace_lm.scope_map(HLO)
        k = trace_lm.reduce(devices, "^jit_train_", scopes)
    assert trace_lm.SCOPES == kept
    assert scopes == {"fusion.1": "block.norm", "fusion.2": "attn.proj_in",
                      "fusion.4": "optimizer"}
    # what the driver gets is `trace_lm.reduce`'s own
    assert k["scope_s"]["optimizer"] == pytest.approx(0.006)
    out = scope_shares.shares(seen)
    assert set(out["scopes"]) == set(scope_shares.SCOPES)
    assert out["step_ms"] == pytest.approx(20.0)
    norm, proj = out["scopes"]["block.norm"], out["scopes"]["attn.proj_in"]
    assert norm["ms"] == pytest.approx(4.0) and norm["remat_ms"] == 0
    assert proj["ms"] == pytest.approx(6.0)
    assert proj["remat_ms"] == pytest.approx(6.0)
    assert proj["share"] == pytest.approx(30.0)
    assert out["scopes"]["attn.full"]["ms"] == 0
    assert out["unscoped"]["ms"] == pytest.approx(2.0)
    assert out["remat_ms"] == pytest.approx(6.0)
    [loose] = out["unscoped_top"]
    assert loose["op"].startswith("%copy.3") and loose["ms"] == pytest.approx(2.0)
    assert loose["op_name"] == "jit(train_step)/jvp()/reshape"
    assert len(out["program_sha256"]) == 64


def test_the_program_text_leaves_out_source_information_and_numbering():
    text = ("HloModule m\n\nFileNames\n1 \"a.py\"\n\nStackFrames\n1 {x}\n\n"
            + HLO)
    other = (HLO.replace("block.norm", "somewhere else")
             .replace("stack_frame_id=3", "stack_frame_id=9")
             .replace("%copy.3", "%copy.8").replace("%fusion.4", "%fusion.11"))
    assert scope_shares.program_text(text) == scope_shares.program_text(
        "HloModule m\n\n" + other)
    assert "metadata" not in scope_shares.program_text(text)
    assert scope_shares.program_text(HLO) != scope_shares.program_text(
        HLO.replace("copy(%p)", "negate(%p)"))


def _kernel(line: int, op: str = "addi") -> str:
    """A module serialized as a Pallas kernel's body is: base64 bytecode,
    its one operation located at `line` of a caller."""
    import base64
    import io

    from jax._src.interpreters import mlir
    from jaxlib.mlir import ir

    with mlir.make_ir_context():
        module = ir.Module.parse(
            "func.func @k(%a: i32) -> i32 {\n"
            f'  %b = arith.{op} %a, %a : i32 loc("decoder.py":{line}:8)\n'
            "  return %b : i32\n}")
        buf = io.BytesIO()
        module.operation.write_bytecode(buf)
    return base64.b64encode(buf.getvalue()).decode()


def test_a_kernels_body_is_compared_without_the_callers_lines():
    def call(body):
        return ('  %zoo_flash_fwd.1 = bf16[8]{0} custom-call(%p), custom_call_'
                'target="tpu_custom_call", frontend_attributes={kernel_metadata'
                '={}}, backend_config={"custom_call_config":{"body":"'
                + body + '"}}')

    here, moved = call(_kernel(185)), call(_kernel(190))
    assert here != moved
    assert scope_shares.program_text(here) == scope_shares.program_text(moved)
    assert "kernel_metadata={}" in scope_shares.program_text(here)
    assert scope_shares.program_text(here) != scope_shares.program_text(
        call(_kernel(185, "muli")))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    made = tiny.make_root(tmp_path_factory.mktemp("bench_scopes"))
    tiny_lm.add_cell(made)
    return made


def test_a_run_of_a_tiny_decoder_cell_prints_every_scope(root, capsys):
    rc = scope_shares.main(["--workload", tiny_lm.CELL, "--seed", str(SEED),
                            "--seconds", "0.5"], root=root, any_platform=True)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["scopes"]) == set(scope_shares.SCOPES)
    assert len(line["scopes"]) == 17
    for entry in list(line["scopes"].values()) + [line["unscoped"]]:
        assert set(entry) == {"ms", "share", "remat_ms"}
    assert "train_tokens_per_s_per_chip" in line["end_to_end"]
    assert trace_lm.scope_map.__name__ == "scope_map"        # put back

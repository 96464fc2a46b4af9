"""The program's named scopes in its compiled step, and the join from a
device event's instruction to its layer (``bench/scopes.py``)."""
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

import _scopes_worker as worker
from bench.scopes import layer, op_names, optimizer_part

WORKER = Path(__file__).resolve().parent / "_scopes_worker.py"
# the join may leave this share of a step's device operations unattributed
LOOSE_SHARE = 0.02


@pytest.mark.parametrize("path,want", [
    ("jit(train_step)/jvp(forward)/while/body/closed_call/dot_general",
     "forward"),
    ("jit(train_step)/transpose(jvp(forward))/while/body/closed_call/"
     "checkpoint/rematted_computation/dot_general", "backward"),
    ("jit(train_step)/shard_map/transpose(jvp(forward))/mul", "backward"),
    ("jit(train_step)/optimizer/bucket_1280x10240/rmnp_rownorm_apply",
     "optimizer"),
    ("jit(train_step)/clip/reduce_sum", "clip"),
    ("jit(train_step)/guard/select_n", "guard"),
    ("jit(train_step)/shard_map/reduce_scatter_64x64/reduce_scatter",
     "reduce_scatter"),
    ("jit(train_step)/ge", ""),
    ("", ""),
])
def test_layer_of_a_path(path, want):
    assert layer(path) == want


@pytest.mark.parametrize("path,want", [
    ("jit(s)/optimizer/gather/concatenate", "gather"),
    ("jit(s)/optimizer/bucket_64x64/all_gather_64x64/all_gather",
     "bucket_64x64"),
    ("jit(s)/optimizer/adamw/mul", "adamw"),
    ("jit(s)/optimizer/add", ""),
    ("jit(s)/clip/add", ""),
])
def test_optimizer_part(path, want):
    assert optimizer_part(path) == want


HLO = """\
HloModule jit_train_step

%fused_computation.1 (param_0: bf16[8]) -> f32[8] {
  %param_0 = bf16[8]{0} parameter(0)
  ROOT %convert.1 = f32[8]{0} convert(%param_0), metadata={op_name="jit(train_step)/optimizer/gather/convert_element_type"}
}

%body.2 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.3 = f32[8]{0} get-tuple-element(%arg), index=1
  %copy.4 = f32[8]{0} copy(%get-tuple-element.3)
  ROOT %tuple.5 = (s32[], f32[8]{0}) tuple(%get-tuple-element.3, %copy.4)
}

ENTRY %main.6 (p0: bf16[8], p1: (s32[], f32[8])) -> f32[8] {
  %p0 = bf16[8]{0} parameter(0), metadata={op_name="params"}
  %p1 = (s32[], f32[8]{0}) parameter(1)
  %copy.7 = bf16[8]{0} copy(%p0)
  %convert_fusion.8 = f32[8]{0} fusion(%copy.7), kind=kLoop, calls=%fused_computation.1
  %while.9 = (s32[], f32[8]{0}) while(%p1), condition=%cond.3, body=%body.2, metadata={op_name="jit(train_step)/transpose(jvp(forward))/while"}
  %copy.10 = f32[8]{0} copy(%convert_fusion.8)
  ROOT %add.11 = f32[8]{0} add(%copy.10, %copy.10), metadata={op_name="jit(train_step)/add"}
}
"""


def test_metadata_less_instructions_take_the_path_of_what_they_compute():
    names = op_names(HLO)
    # a fusion by its fused computation, a copy in a loop body by the loop
    assert layer(names["convert_fusion.8"]) == "optimizer"
    assert layer(names["copy.4"]) == "backward"
    # entry copies by their operand (copy.10) or user (copy.7)
    assert optimizer_part(names["copy.10"]) == "gather"
    assert optimizer_part(names["copy.7"]) == "gather"
    # a path of its own that names a layer is kept; one that names none
    # is passed over like a missing one
    assert names["while.9"].endswith("transpose(jvp(forward))/while")
    assert optimizer_part(names["add.11"]) == "gather"
    assert op_names(HLO.replace("%copy.10, %copy.10", "%p0, %p0"))[
        "add.11"] == "jit(train_step)/add"


def _check(s, want):
    assert set(want) <= set(s["parts"]), sorted(set(want) - set(s["parts"]))
    assert {"forward", "backward", "optimizer", "clip"} <= set(s["layers"])
    assert len(s["loose"]) <= LOOSE_SHARE * s["ops"], s["loose"]


ONE_CHIP = ["jvp(forward)", "transpose(jvp(forward))", "clip", "optimizer",
            "gather", "scatter", "adamw", "bucket_64x64", "bucket_64x256",
            "bucket_128x64", "bucket_512x64"]


def test_one_chip_step_carries_the_scopes():
    _check(worker.summary(worker.step_hlo("tiny.rmnp.b4s32", 1)), ONE_CHIP)


def test_zero2_step_names_each_buckets_collectives():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(WORKER), "tiny.zero2-fp32.b8s32", "4"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    s = json.loads(proc.stdout.strip().splitlines()[-1])
    keys = ["64x64", "64x256", "128x64", "512x64"]
    _check(s, ONE_CHIP + [f"reduce_scatter_{k}" for k in keys]
           + [f"all_gather_{k}" for k in keys])
    assert "reduce_scatter" in s["layers"]


# ---------------------------------------------------------------------------
# the readers on a recorded chip trace of the program with its scopes
# ---------------------------------------------------------------------------

RECORDED = Path(__file__).resolve().parent / "recorded_scopes.json"
READERS = ["forward_ms", "backward_ms", "optimizer_ms", "data_wait_ms",
           "data_host_ms", "window_compiles"]


def _ctx(rec, report):
    from bench.trace import Event, Reduction

    events = [Event(0, line, name, float(s), float(d))
              for line, name, s, d in rec["events"]]
    host = [Event(-1, line, name, float(s), float(d))
            for line, name, s, d in rec["host"]]
    return SimpleNamespace(trace=Reduction(events, host), report=report)


def _report(rec):
    hlo = "ENTRY %main.1 () -> f32[] {\n" + "".join(
        f'  %{name} = f32[] fusion(), metadata={{op_name="{path}"}}\n'
        for name, path in rec["op_names"].items()) + "}\n"
    return SimpleNamespace(hlo_text=hlo, spans=rec["spans"],
                           compiles=rec["compiles"])


def _read(name, ctx):
    from bench import harness
    return harness.load_reader(name)(ctx)


def _compute(rec):
    from bench.trace import MODULES, OPS
    runs = [e for e in rec["events"] if e[0] == MODULES]
    lo = min(s for _, _, s, _ in runs)
    hi = max(s + d for _, _, s, d in runs)
    return lo, hi, [e for e in rec["events"] if e[0] == OPS
                    and not e[1].startswith("while") and lo <= e[2] < hi]


def test_readers_on_the_recorded_chip_trace():
    rec = json.loads(RECORDED.read_text())
    ctx = _ctx(rec, _report(rec))
    lo, hi, ops = _compute(rec)
    steps = 2

    def ms(keep):
        return sum(d for _, name, _, d in ops
                   if keep(rec["op_names"][name])) / (1e6 * steps)

    want = {
        "forward_ms": ms(lambda p: "jvp(forward)" in p
                         and "transpose(" not in p),
        "backward_ms": ms(lambda p: "transpose(jvp(forward))" in p),
        "optimizer_ms": ms(lambda p: "/optimizer/" in p),
    }
    for name, value in want.items():
        assert value > 0
        assert _read(name, ctx) == pytest.approx(value, rel=1e-12)
    kernel = _read("rmnp_kernel_ms", ctx)
    assert 22.0 < kernel < 23.2 and _read("optimizer_ms", ctx) > kernel

    # instants of the window that no recorded operation covers, under a
    # host ``data`` span, by a sweep over 1 us cells (the recording keeps
    # only the longer operations, so more of its window reads idle)
    cover = set()
    for _, _, s, d in ops:
        cover.update(range(int(s // 1000), int(-(-(s + d) // 1000))))
    data = [(s, s + d) for _, name, s, d in rec["host"] if name == "data"]
    idle_under_data = sum(
        1 for t in range(int(lo // 1000), int(hi // 1000))
        if t not in cover and any(a <= t * 1000 and (t + 1) * 1000 <= b
                                  for a, b in data))
    assert _read("data_wait_ms", ctx) == pytest.approx(
        idle_under_data / 1000 / steps, rel=0.01)

    window = [(t1 - t0) / 1e6 for name, _, step, t0, t1 in rec["spans"]
              if name == "data" and step > 2]
    assert len(window) == 12
    assert _read("data_host_ms", ctx) == pytest.approx(
        sum(window) / len(window))
    # the set-up iterations compile the benchmark's own readers; the
    # window's steps compile nothing
    assert [n for _, n in rec["compiles"][:3]] == [1, 1, 1]
    assert _read("window_compiles", ctx) == 0


def test_readers_report_nothing_for_a_program_without_scopes_or_spans():
    rec = json.loads(RECORDED.read_text())
    parent = dict(rec, host=[h for h in rec["host"] if h[1] != "data"],
                  op_names={k: v.replace("(forward)", "()").replace(
                      "/optimizer/", "/").replace("/clip/", "/")
                      for k, v in rec["op_names"].items()})
    report = SimpleNamespace(hlo_text=_report(parent).hlo_text)
    ctx = _ctx(parent, report)
    assert {name: _read(name, ctx) for name in READERS} == dict.fromkeys(
        READERS)

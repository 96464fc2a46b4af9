"""The reduction from a device trace to per-layer numbers, on small
traces whose answers can be counted by hand."""
import json
from pathlib import Path

import pytest

from bench.trace import MODULES, OPS, Event, Reduction, is_collective, union

STEP = "jit_train_step"


def _ev(dev, line, name, start, dur):
    return Event(dev, line, name, float(start), float(dur))


def _steps(dev, starts, dur=100, name=STEP):
    return [_ev(dev, MODULES, f"{name}(7)", s, dur) for s in starts]


def test_overlapping_operations_count_once():
    events = _steps(0, [0, 100]) + [
        _ev(0, OPS, "fusion.1", 0, 60),
        _ev(0, OPS, "fusion.2", 40, 15),     # inside fusion.1's span
        _ev(0, OPS, "fusion.3", 100, 50),
        _ev(0, OPS, "fusion.4", 140, 30),    # overlaps fusion.3 by 10
    ]
    r = Reduction(events)
    assert r.busy(0) == 60 + 70
    assert abs(r.idle_share() - (1 - 130 / 200)) < 1e-12
    assert r.steps == 2
    assert r.busy_s == 130 / 1e9 and r.window_s == 200 / 1e9


def test_collective_under_compute_is_not_exposed():
    events = _steps(0, [0]) + [
        _ev(0, OPS, "all-reduce.1", 10, 30),          # hidden by fusion.1
        _ev(0, OPS, "fusion.1", 0, 50),
        _ev(0, OPS, "reduce-scatter.2", 60, 20),      # half hidden
        _ev(0, OPS, "convolution.3", 70, 20),
        _ev(0, OPS, "all-gather-start.4", 95, 5),     # alone
    ]
    total, exposed = Reduction(events).collective_exposed(0)
    assert total == 30 + 20 + 5
    assert exposed == 10 + 5


def test_a_collective_fusion_is_exposed_not_compute():
    # fusion.12 runs a reduce-scatter (known from its text when loaded)
    events = _steps(0, [0]) + [
        _ev(0, OPS, "fusion.1", 0, 40),
        Event(0, OPS, "fusion.12", 40.0, 30.0, "", True),
        _ev(0, OPS, "fusion.2", 60, 20),               # hides 10 of it
    ]
    total, exposed = Reduction(events).collective_exposed(0)
    assert (total, exposed) == (30, 20)


def test_steps_outside_the_steady_window_are_excluded():
    # a set-up program and ops before the first step and after the last
    # step lie outside the window
    events = (_steps(0, [100, 200])
              + [_ev(0, MODULES, "jit_init(3)", 0, 50),
                 _ev(0, OPS, "rng.1", 0, 50),
                 _ev(0, OPS, "fusion.1", 100, 80),
                 _ev(0, OPS, "fusion.1", 200, 80),
                 _ev(0, OPS, "copy.9", 310, 20)])
    r = Reduction(events)
    dev = r.devices[0]
    assert (dev.lo, dev.hi, dev.steps) == (100, 300, 2)
    assert r.busy(0) == 160
    ns, evs = r.kernel("fusion")[0]
    assert ns == 160 and len(evs) == 2
    assert r.kernel("rng")[0] == (0, [])


def test_the_step_program_is_the_module_with_most_device_time():
    events = (_steps(0, [0, 100], dur=90)
              + _steps(0, [200, 210, 220, 230], dur=5, name="jit_norms"))
    assert Reduction(events).devices[0].steps == 2


def test_kernel_time_per_chip_and_breakdown():
    events = []
    for dev in (0, 1):
        events += _steps(dev, [0, 100])
        events += [_ev(dev, OPS, "rmnp_rownorm_apply.3", 10 + 100 * i,
                       5 + dev) for i in range(2)]
        events += [_ev(dev, OPS, "fusion.7", 20 + 100 * i, 50)
                   for i in range(2)]
    r = Reduction(events)
    k = r.kernel("rmnp_rownorm_apply")
    assert k[0][0] == 10 and k[1][0] == 12
    b = r.breakdown()
    assert b["device_ops"][0] == ["fusion.7", 100 / 1e9]
    assert b["device_ops"][1] == ["rmnp_rownorm_apply.3", 11 / 1e9]
    # per chip: gaps 0-10, 15/16-20, 70-110, 115/116-120, 170-200
    assert max(g[1] for g in b["idle_gaps"]) == 40 / 1e9


@pytest.mark.parametrize("text,collective", [
    ("%reduce_scatter.49 = f32[1,36,1280,1280]{3,2,1,0:T(8,128)} "
     "reduce-scatter(%maximum_convert_fusion), channel_id=1", True),
    ("%fusion.12 = f32[63106,8,128]{2,1,0:T(8,128)} fusion(%maximum_fusion)"
     ", kind=kCustom, calls=%all-reduce-scatter, backend_config={}", True),
    ("%all-reduce.2 = (f32[7]{0:T(128)S(1)}, f32[]{:T(128)}) "
     "all-reduce(%pad_fusion.23, %copy-done.91), channel_id=4", True),
    ("%all-gather-start.1 = (bf16[9,5120,1280]{2,1,0}, bf16[36,5120,1280]"
     "{2,1,0}) all-gather-start(%get-tuple-element.811)", True),
    ("%fusion.437 = bf16[8,1024,10240]{2,1,0:T(8,128)(2,1)} fusion(bf16["
     "1280,10240]{1,0} %all-gather.16), kind=kOutput, "
     "calls=%fused_computation.93.clone", False),
    ("%rmnp_rownorm_apply.3 = (f32[36,1280,10240]{2,1,0}) custom-call("
     "%all-gather.19), custom_call_target=\"tpu_custom_call\"", False),
    ("reduce_scatter.7", True),
    ("fusion.12", False),
])
def test_collectives_are_known_by_opcode_or_fusion_target(text, collective):
    assert is_collective(text) is collective


def test_union_merges_touching_intervals():
    assert union([(5, 7), (0, 2), (2, 3), (6, 9)]) == [(0, 3), (5, 9)]


RECORDED = Path(__file__).resolve().parent / "recorded_trace.json"


def _recorded():
    rows = json.loads(RECORDED.read_text())["events"]
    return [Event(0, line, name, float(s), float(d))
            for line, name, s, d in rows]


def _sweep(intervals):
    """Covered length by an endpoint sweep (independent of ``union``)."""
    points = sorted([(s, 1) for s, _ in intervals]
                    + [(e, -1) for _, e in intervals])
    depth, last, total = 0, 0.0, 0.0
    for t, step in points:
        if depth > 0:
            total += t - last
        depth, last = depth + step, t
    return total


def test_recorded_chip_trace():
    events = _recorded()
    r = Reduction(events)
    dev = r.devices[0]
    runs = sorted((e for e in events if e.line == MODULES
                   and e.name.startswith("jit_train_step")),
                  key=lambda e: e.start)
    # the window is the three step runs; the tail of the step before
    # (its last fusions and RMNP launch) lies outside it
    assert (dev.steps, dev.lo) == (3, runs[0].start)
    assert dev.hi == runs[-1].start + runs[-1].dur
    assert any(e.start < dev.lo for e in events if e.line == OPS)
    assert all(dev.lo <= e.start < dev.hi for e in dev.ops)
    # layer loops span the fusions inside them and async copies overlap
    # compute: each instant counts once, loops and copies not at all
    compute = [(e.start, min(e.start + e.dur, dev.hi)) for e in events
               if e.line == OPS and not e.name.startswith("while")
               and dev.lo <= e.start < dev.hi]
    assert r.busy(0) == _sweep(compute)
    assert sum(e.dur for e in dev.ops) > dev.hi - dev.lo > r.busy(0)
    # three RMNP launches per step (the block buckets), 22.5 ms a step
    ns, launches = r.kernel("rmnp_rownorm_apply")[0]
    assert len(launches) == 9
    assert 22.0e6 < ns / 3 < 23.2e6

"""The training loop's host spans and compile counter
(``repro.launch.spans``), recorded through whole CPU runs of ``train()``."""
import jax
import pytest

from repro.launch.spans import Spans
from repro.launch.train import StepReport, train

SMALL = dict(steps=4, batch=2, seq=16, log_every=2, fused_apply=True,
             use_kernel=True)


def _by_step(spans):
    out = {}
    for name, parent, step, t0, t1 in spans:
        out.setdefault(step, []).append((name, parent, t0, t1))
    return out


def test_each_iteration_is_a_train_step_span_with_its_children():
    rep = StepReport()
    train("gpt2-60m", "rmnp", report=rep, **SMALL)
    steps = _by_step(rep.spans)
    assert sorted(steps) == [-1, 0, 1, 2, 3]
    for step in range(4):
        (top,) = [s for s in steps[step] if s[0] == "train_step"]
        assert top[1] is None
        children = [s for s in steps[step] if s[0] != "train_step"]
        # logged steps (every 2nd, and the last) block on the device
        want = ["data", "dispatch"] + (["block"] if step in (0, 2, 3)
                                       else [])
        assert [c[0] for c in children] == want
        for name, parent, t0, t1 in children:
            assert parent == "train_step"
            assert top[2] <= t0 <= t1 <= top[3]
    setup = {name: (parent, t1 - t0) for name, parent, t0, t1 in steps[-1]}
    assert set(setup) == {"setup/trace_optimizer", "setup/compile",
                          "setup/init_state"}
    assert all(parent is None for parent, _ in setup.values())
    # one clock: the compile time is the compile span's
    assert rep.compile_s == setup["setup/compile"][1] / 1e9
    assert [s for s, _ in rep.compiles] == [0, 1, 2, 3]
    assert all(n == 0 for _, n in rep.compiles[1:])
    assert set(rep.routes) and all(ln is not None
                                   for ln in rep.routes.values())


def test_a_guard_rewind_recompiles_once_at_the_step_it_rewinds_to(
        tmp_path):
    rep = StepReport()
    train("gpt2-60m", "rmnp", steps=8, batch=2, seq=16, log_every=1,
          guard=True, inject_fault="nan:*:4+", anomaly_skip_budget=1,
          anomaly_rewind_budget=1, anomaly_lr_backoff=1.0,
          anomaly_health_window=1, ckpt_dir=str(tmp_path), ckpt_every=2,
          report=rep)
    iters = [s for s, _ in rep.compiles]
    # steps 4 and 5 are skipped, the second skip rewinds to the good
    # checkpoint of step 2, and the disarmed step is built anew there
    assert iters == [0, 1, 2, 3, 4, 5, 2, 3, 4, 5, 6, 7]
    counts = [n for _, n in rep.compiles]
    assert counts[1:] == [0, 0, 0, 0, 0, 1, 0, 0, 0, 0, 0]
    names = {name for name, *_ in rep.spans}
    assert {"guard", "checkpoint"} <= names
    compiles = [s for s in rep.spans if s[0] == "setup/compile"]
    assert [(s[1], s[2]) for s in compiles] == [(None, -1),
                                                ("train_step", 2)]


def _duration_listeners():
    from jax._src import monitoring
    return list(monitoring.get_event_duration_listeners())


def test_without_a_report_nothing_is_recorded_or_counted():
    spans = Spans(None)
    before = _duration_listeners()
    with spans:
        assert _duration_listeners() == before
        with spans.iteration(0), spans.span("data") as s:
            pass
    assert s.seconds >= 0 and spans.open == []
    train("gpt2-60m", "rmnp", steps=2, batch=2, seq=16, log_every=1)
    assert _duration_listeners() == before


def test_the_counter_counts_compiles_only_while_it_is_entered():
    rep = StepReport()
    spans = Spans(rep)
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    x = jax.numpy.ones(5)
    with spans:
        with spans.iteration(7):
            f(x).block_until_ready()
        with spans.iteration(8):
            f(x).block_until_ready()
    jax.jit(lambda x: x - 2.0)(1.0)
    assert rep.compiles[-2:] == [(7, 1), (8, 0)]


@pytest.mark.parametrize("name", ["data", "block"])
def test_a_span_closes_and_records_when_its_body_raises(name):
    rep = StepReport()
    spans = Spans(rep)
    with pytest.raises(KeyError), spans.span(name):
        raise KeyError(name)
    assert [(n, p, s) for n, p, s, *_ in rep.spans] == [(name, None, -1)]
    assert spans.open == []


def test_attention_routes_recorded_and_logged_once_at_setup(capsys):
    """Each attention call site's route sits in ``StepReport.routes`` beside
    the kernel routes, and is logged once, at setup."""
    rep = StepReport()
    train("gpt2-60m", "rmnp", report=rep, **SMALL)
    attention = {k: r for k, r in rep.routes.items()
                 if k.startswith("attention ")}
    assert list(attention.values()) == ["dense: backend cpu"]
    (key,) = attention
    assert key.startswith("attention (2,16,")
    out = capsys.readouterr().out
    assert out.count(f"[train] {key}: dense: backend cpu") == 1

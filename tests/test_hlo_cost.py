"""Tests for the trip-count-aware HLO cost analyzer (launch/hlo_cost.py).

XLA's cost_analysis() counts while bodies once; these tests pin the
analyzer's loop multipliers against programs with known FLOP counts.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import (
    HloCostAnalyzer, analyze_hlo, parse_module, shape_bytes, shape_elems,
)


def _analyze(fn, *sds):
    return analyze_hlo(jax.jit(fn).lower(*sds).compile().as_text())


def F32(*s):
    return jax.ShapeDtypeStruct(s, jnp.float32)


def test_plain_matmul_flops_exact():
    r = _analyze(lambda a, b: a @ b, F32(256, 512), F32(512, 128))
    assert r["flops"] == 2 * 256 * 512 * 128


def test_scan_multiplies_body():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y
    r = _analyze(f, F32(8, 16), F32(16, 16))
    exact = 7 * 2 * 8 * 16 * 16
    assert exact <= r["flops"] <= exact * 1.2


def test_nested_scan_multiplies_product():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y
    r = _analyze(f, F32(8, 16), F32(16, 16))
    exact = 15 * 2 * 8 * 16 * 16
    assert exact <= r["flops"] <= exact * 1.2


def test_elementwise_and_transcendentals_counted():
    r = _analyze(lambda x: jnp.exp(x) + x, F32(128, 128))
    assert r["flops"] >= 2 * 128 * 128 * 0.9
    assert r["transcendentals"] >= 128 * 128 * 0.9


def test_bytes_scale_with_scan_length():
    def mk(n):
        def f(x):
            def body(c, _):
                return jnp.tanh(c) * 2.0, None
            y, _ = jax.lax.scan(body, x, None, length=n)
            return y
        return f
    r2 = _analyze(mk(2), F32(64, 256))
    r20 = _analyze(mk(20), F32(64, 256))
    assert r20["bytes_accessed"] > 5 * r2["bytes_accessed"]


def test_shape_helpers():
    assert shape_bytes("f32[8,16]{1,0}") == 8 * 16 * 4
    assert shape_bytes("(s32[], f32[4,4]{1,0})") == 4 + 64
    assert shape_elems("bf16[10,10]") == 100


def test_parse_module_entry_and_trip_count():
    hlo = """
%cond (arg: (s32[], f32[4])) -> pred[] {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %c = s32[] constant(11)
  %i = s32[] get-tuple-element(%arg), index=0
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[4]{0} get-tuple-element(%arg), index=1
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  %x2 = f32[4]{0} multiply(%x, %x)
  ROOT %t = (s32[], f32[4]{0}) tuple(%i2, %x2)
}

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[4]{0}) tuple(%z, %p)
  %w = (s32[], f32[4]{0}) while(%init), condition=%cond, body=%body
  ROOT %out = f32[4]{0} get-tuple-element(%w), index=1
}
"""
    comps, entry = parse_module(hlo)
    assert entry == "main"
    assert set(comps) == {"cond", "body", "main"}
    an = HloCostAnalyzer(hlo)
    assert an.trip_count("cond") == 11
    cost = an.analyze()
    # 11 iterations x (4 multiply flops + 1 add flop)
    assert cost.flops == 11 * 5


def test_collective_wire_model():
    hlo = """
ENTRY %main (p: f32[128]) -> f32[128] {
  %p = f32[128]{0} parameter(0)
  ROOT %ar = f32[128]{0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%add
}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}
"""
    r = analyze_hlo(hlo)
    assert r["collectives"]["all-reduce"]["count"] == 1
    # ring all-reduce: 2 * bytes * (g-1)/g = 2 * 512 * 3/4
    assert r["collective_wire_bytes"] == pytest.approx(2 * 512 * 3 / 4)


def test_dynamic_update_slice_counts_slice_only():
    def f(big, small):
        return jax.lax.dynamic_update_slice(big, small, (0, 0))
    # donate the buffer: without donation XLA inserts a full copy (real
    # traffic the analyzer must — and does — count)
    c = jax.jit(f, donate_argnums=(0,)).lower(
        F32(4096, 4096), F32(8, 8)).compile()
    r = analyze_hlo(c.as_text())
    # DUS traffic should be ~2x the slice, not the 64MiB operand
    assert r["bytes_accessed"] < 4096 * 4096 * 4


def test_breakdown_matches_analyze_totals():
    from repro.launch.hlo_cost import breakdown
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y
    c = jax.jit(f).lower(F32(32, 64), F32(64, 64)).compile()
    txt = c.as_text()
    agg, top = breakdown(txt)
    total = sum(agg.values())
    r = analyze_hlo(txt)
    # breakdown's per-op attribution must sum to the analyzer's bytes
    # (collectives add local r/w in analyze; none here)
    assert abs(total - r["bytes_accessed"]) / max(r["bytes_accessed"], 1) < 1e-6
    assert top and top[0][0] > 0


# ---------------------------------------------------------------------------
# collective_overlap_report: the pipelined-ZeRO-2 structure checker
# ---------------------------------------------------------------------------

_BUCKETS = [("8x16", 8, 16), ("8x24", 8, 24)]

_PIPELINED_HLO = """
ENTRY %step (p0: f32[4,2,8,16], q0: f32[4,1,8,24]) -> f32[8,8,16] {
  %p0 = f32[4,2,8,16]{3,2,1,0} parameter(0)
  %q0 = f32[4,1,8,24]{3,2,1,0} parameter(1)
  %rs1 = f32[2,8,16]{2,1,0} reduce-scatter(%p0), replica_groups={{0,1,2,3}}
  %rs2 = f32[1,8,24]{2,1,0} reduce-scatter(%q0), replica_groups={{0,1,2,3}}
  %upd1 = f32[2,8,16]{2,1,0} multiply(%rs1, %rs1)
  %upd2 = f32[1,8,24]{2,1,0} multiply(%rs2, %rs2)
  %ag1 = f32[8,8,16]{2,1,0} all-gather(%upd1), replica_groups={{0,1,2,3}}
  %ag2 = f32[4,8,24]{2,1,0} all-gather(%upd2), replica_groups={{0,1,2,3}}
  ROOT %out = f32[8,8,16]{2,1,0} add(%ag1, %ag1)
}
"""

# bucket 8x24's collective consumes bucket 8x16's updated-weight gather —
# the serialization the pipelined step must never produce
_SERIALIZED_HLO = """
ENTRY %step (p0: f32[4,2,8,16], q0: f32[4,1,8,24]) -> f32[8,8,16] {
  %p0 = f32[4,2,8,16]{3,2,1,0} parameter(0)
  %q0 = f32[4,1,8,24]{3,2,1,0} parameter(1)
  %rs1 = f32[2,8,16]{2,1,0} reduce-scatter(%p0), replica_groups={{0,1,2,3}}
  %upd1 = f32[2,8,16]{2,1,0} multiply(%rs1, %rs1)
  %ag1 = f32[8,8,16]{2,1,0} all-gather(%upd1), replica_groups={{0,1,2,3}}
  %gate = f32[] custom-call(%ag1), custom_call_target="Sink"
  %mix = f32[4,1,8,24]{3,2,1,0} custom-call(%q0, %gate), custom_call_target="Gate"
  %rs2 = f32[1,8,24]{2,1,0} reduce-scatter(%mix), replica_groups={{0,1,2,3}}
  %upd2 = f32[1,8,24]{2,1,0} multiply(%rs2, %rs2)
  %ag2 = f32[4,8,24]{2,1,0} all-gather(%upd2), replica_groups={{0,1,2,3}}
  ROOT %out = f32[8,8,16]{2,1,0} add(%ag1, %ag1)
}
"""


def test_overlap_report_clean_pipeline_has_no_edges():
    from repro.launch.hlo_cost import collective_overlap_report

    r = collective_overlap_report(_PIPELINED_HLO, _BUCKETS)
    assert len(r["collectives"]) == 2
    assert {c["bucket"] for c in r["collectives"]} == {"8x16", "8x24"}
    assert len(r["update_gathers"]) == 2
    assert r["n_serialization_edges"] == 0


def test_overlap_report_detects_cross_bucket_serialization():
    from repro.launch.hlo_cost import collective_overlap_report

    r = collective_overlap_report(_SERIALIZED_HLO, _BUCKETS)
    assert r["n_serialization_edges"] == 1
    (u, c, bu, bc) = r["serialization_edges"][0]
    assert (u, c, bu, bc) == ("ag1", "rs2", "8x16", "8x24")


def test_overlap_report_tracks_deps_through_while_loops():
    """An update gather feeding a while body that feeds a collective is
    still a serialization edge (conservative transitive ancestry through
    called computations)."""
    from repro.launch.hlo_cost import collective_overlap_report

    hlo = """
%body (arg: (s32[], f32[4,1,8,24])) -> (s32[], f32[4,1,8,24]) {
  %arg = (s32[], f32[4,1,8,24]{3,2,1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[4,1,8,24]{3,2,1,0} get-tuple-element(%arg), index=1
  ROOT %t = (s32[], f32[4,1,8,24]{3,2,1,0}) tuple(%i, %x)
}
%cond (arg: (s32[], f32[4,1,8,24])) -> pred[] {
  %arg = (s32[], f32[4,1,8,24]{3,2,1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %n = s32[] constant(3)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}
ENTRY %step (p0: f32[4,2,8,16], q0: f32[4,1,8,24]) -> f32[8,8,16] {
  %p0 = f32[4,2,8,16]{3,2,1,0} parameter(0)
  %q0 = f32[4,1,8,24]{3,2,1,0} parameter(1)
  %rs1 = f32[2,8,16]{2,1,0} reduce-scatter(%p0), replica_groups={{0,1,2,3}}
  %upd1 = f32[2,8,16]{2,1,0} multiply(%rs1, %rs1)
  %ag1 = f32[8,8,16]{2,1,0} all-gather(%upd1), replica_groups={{0,1,2,3}}
  %zero = s32[] constant(0)
  %seed = f32[4,1,8,24]{3,2,1,0} custom-call(%q0, %ag1), custom_call_target="Mix"
  %init = (s32[], f32[4,1,8,24]{3,2,1,0}) tuple(%zero, %seed)
  %loop = (s32[], f32[4,1,8,24]{3,2,1,0}) while(%init), condition=%cond, body=%body
  %mix = f32[4,1,8,24]{3,2,1,0} get-tuple-element(%loop), index=1
  %rs2 = f32[1,8,24]{2,1,0} reduce-scatter(%mix), replica_groups={{0,1,2,3}}
  ROOT %out = f32[8,8,16]{2,1,0} add(%ag1, %ag1)
}
"""
    r = collective_overlap_report(hlo, _BUCKETS)
    assert r["n_serialization_edges"] == 1
    assert r["serialization_edges"][0][:2] == ("ag1", "rs2")


def test_overlap_report_on_real_sharded_update():
    """Compiled single-device shard_map program: the per-bucket chains of
    update_apply_sharded produce update gathers for every bucket and no
    serialization edges."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.core import make_optimizer
    from repro.core.bucketing import gather_chunks
    from repro.distributed.compression import exact_reduce_scatter
    from repro.launch.hlo_cost import collective_overlap_report
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(1)
    opt = make_optimizer("rmnp", lr_matrix=0.1, beta=0.9,
                         shard_axis="data", shard_size=1)
    params = {"a/w": jnp.ones((4, 8, 16), jnp.float32),
              "b/w": jnp.ones((2, 8, 24), jnp.float32)}
    grads = {k: jnp.full_like(v, 0.5) for k, v in params.items()}
    state = opt.init(params)
    plan = opt.bucket_plan(params)

    def step(g, s, p):
        chunks = gather_chunks(plan, g, 1, dtype=jnp.float32)
        shards = {b.key: exact_reduce_scatter(chunks[b.key], "data")
                  for b in plan.buckets}
        return opt.update_apply_sharded(shards, g, s, p, 0)

    fn = shard_map(step, mesh=mesh, in_specs=(P(), P(), P()),
                   out_specs=(P(), P()), check_vma=False)
    hlo = jax.jit(fn).lower(grads, state, params).compile().as_text()
    r = collective_overlap_report(
        hlo, [(b.key, b.d_in, b.d_out) for b in plan.buckets])
    assert r["n_serialization_edges"] == 0


def test_overlap_report_survives_deep_operand_chains():
    """Real HLO modules run operand chains tens of thousands of ops deep;
    the reachability walk must be iterative (a recursive walk dies in
    RecursionError around ~1000 hops) and still find the edge at the far
    end of the chain."""
    from repro.launch.hlo_cost import collective_overlap_report

    chain = "\n".join(
        f"  %c{i} = f32[4,1,8,24]{{3,2,1,0}} add(%c{i - 1}, %c{i - 1})"
        for i in range(1, 3000))
    hlo = f"""
ENTRY %step (p0: f32[4,2,8,16], q0: f32[4,1,8,24]) -> f32[8,8,16] {{
  %p0 = f32[4,2,8,16]{{3,2,1,0}} parameter(0)
  %q0 = f32[4,1,8,24]{{3,2,1,0}} parameter(1)
  %rs1 = f32[2,8,16]{{2,1,0}} reduce-scatter(%p0), replica_groups={{{{0,1,2,3}}}}
  %upd1 = f32[2,8,16]{{2,1,0}} multiply(%rs1, %rs1)
  %ag1 = f32[8,8,16]{{2,1,0}} all-gather(%upd1), replica_groups={{{{0,1,2,3}}}}
  %c0 = f32[4,1,8,24]{{3,2,1,0}} custom-call(%q0, %ag1), custom_call_target="Mix"
{chain}
  %rs2 = f32[1,8,24]{{2,1,0}} reduce-scatter(%c2999), replica_groups={{{{0,1,2,3}}}}
  ROOT %out = f32[8,8,16]{{2,1,0}} add(%ag1, %ag1)
}}
"""
    r = collective_overlap_report(hlo, _BUCKETS)
    assert r["n_serialization_edges"] == 1
    assert r["serialization_edges"][0][:2] == ("ag1", "rs2")


def test_overlap_report_sees_collective_inside_loop_body():
    """A collective nested in a while body whose loop init consumes an
    update gather is still a serialization edge: the graph links caller ->
    called-computation ops too (conservative), so sinking a collective
    into a loop cannot make the checker pass vacuously."""
    from repro.launch.hlo_cost import collective_overlap_report

    hlo = """
%body (arg: (s32[], f32[4,1,8,24])) -> (s32[], f32[4,1,8,24]) {
  %arg = (s32[], f32[4,1,8,24]{3,2,1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[4,1,8,24]{3,2,1,0} get-tuple-element(%arg), index=1
  %rs2 = f32[1,8,24]{2,1,0} reduce-scatter(%x), replica_groups={{0,1,2,3}}
  %y = f32[4,1,8,24]{3,2,1,0} broadcast(%rs2), dimensions={1,2,3}
  ROOT %t = (s32[], f32[4,1,8,24]{3,2,1,0}) tuple(%i, %y)
}
%cond (arg: (s32[], f32[4,1,8,24])) -> pred[] {
  %arg = (s32[], f32[4,1,8,24]{3,2,1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %n = s32[] constant(3)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}
ENTRY %step (p0: f32[4,2,8,16], q0: f32[4,1,8,24]) -> f32[8,8,16] {
  %p0 = f32[4,2,8,16]{3,2,1,0} parameter(0)
  %q0 = f32[4,1,8,24]{3,2,1,0} parameter(1)
  %rs1 = f32[2,8,16]{2,1,0} reduce-scatter(%p0), replica_groups={{0,1,2,3}}
  %upd1 = f32[2,8,16]{2,1,0} multiply(%rs1, %rs1)
  %ag1 = f32[8,8,16]{2,1,0} all-gather(%upd1), replica_groups={{0,1,2,3}}
  %zero = s32[] constant(0)
  %seed = f32[4,1,8,24]{3,2,1,0} custom-call(%q0, %ag1), custom_call_target="Mix"
  %init = (s32[], f32[4,1,8,24]{3,2,1,0}) tuple(%zero, %seed)
  %loop = (s32[], f32[4,1,8,24]{3,2,1,0}) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8,8,16]{2,1,0} add(%ag1, %ag1)
}
"""
    r = collective_overlap_report(hlo, _BUCKETS)
    assert any(e[:2] == ("ag1", "rs2") for e in r["serialization_edges"]), r

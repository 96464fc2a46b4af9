"""Tests for the static analysis subsystem (src/repro/analysis/).

Each pass gets hand-written synthetic HLO fixtures — one known-good and
one known-violating module — so the checkers are pinned against exact
textual shapes, independent of what XLA happens to emit today.  The
4-device registry sweep and the deliberately-broken lowerings run in a
subprocess (tests/_analysis_worker.py) because the device-count env var
must be set before jax imports.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import hlo as H
from repro.analysis.conventions import scan_file
from repro.analysis.donation import DonationPass
from repro.analysis.findings import (
    Finding, Severity, apply_allowlist, report_dict,
)
from repro.analysis.framework import (
    Artifacts, BucketMeta, Combo, DonatedLeaf, pass_catalog, run_passes,
)
from repro.analysis.memory import MemoryPass, count_jaxpr_buffers
from repro.analysis.overlap import OverlapPass, collective_overlap_report
from repro.analysis.sharding import ShardingPass, classify_all_gathers

# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

BUCKET = BucketMeta(
    key="64x64", d_in=64, d_out=64, size=3, padded=4,
    momentum_dtype="float32",
    slot_shapes={"nu": ((4, 1, 64), "float32")},
    leaf_shapes=((64, 64), (64, 64), (64, 64)))


def _art(hlo="", combo=None, **kw):
    return Artifacts(combo=combo or Combo("rmnp", "fp32"),
                     hlo_text=hlo, **kw)


def _errors(findings):
    return [f for f in findings if f.severity is Severity.ERROR]


# one legitimate updated-weight gather; momentum stays sharded
GOOD_ZERO2 = textwrap.dedent("""\
    ENTRY %main (p0: f32[1,64,64]) -> f32[4,64,64] {
      %p0 = f32[1,64,64]{2,1,0} parameter(0)
      %rs = f32[1,64,64] reduce-scatter(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
      %upd = f32[1,64,64]{2,1,0} add(%rs, %rs)
      ROOT %ag = f32[4,64,64]{2,1,0} all-gather(%upd), replica_groups={{0,1,2,3}}, dimensions={0}
    }

    %add (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      %b = f32[] parameter(1)
      ROOT %s = f32[] add(%a, %b)
    }
    """)

# a second full-bucket gather (replicated momentum) and a slot gather
BAD_ZERO2 = textwrap.dedent("""\
    ENTRY %main (p0: f32[1,64,64], p1: f32[1,1,64]) -> f32[4,64,64] {
      %p0 = f32[1,64,64]{2,1,0} parameter(0)
      %p1 = f32[1,1,64]{2,1,0} parameter(1)
      %rs = f32[1,64,64] reduce-scatter(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
      %mom = f32[4,64,64]{2,1,0} all-gather(%rs), replica_groups={{0,1,2,3}}, dimensions={0}
      %slot = f32[4,1,64]{2,1,0} all-gather(%p1), replica_groups={{0,1,2,3}}, dimensions={0}
      %upd = f32[1,64,64]{2,1,0} slice(%mom), slice={[0:1], [0:64], [0:64]}
      ROOT %ag = f32[4,64,64]{2,1,0} all-gather(%upd), replica_groups={{0,1,2,3}}, dimensions={0}
    }

    %add (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      %b = f32[] parameter(1)
      ROOT %s = f32[] add(%a, %b)
    }
    """)


# ---------------------------------------------------------------------------
# hardened parser
# ---------------------------------------------------------------------------

class TestParserHardening:
    def test_tuple_result_types(self):
        assert H.shape_bytes("(s32[], f32[4,4]{1,0})") == 4 + 64
        assert H.all_shapes("(f32[1,8]{1,0}, f32[4,8]{1,0})") == [
            ("f32", (1, 8)), ("f32", (4, 8))]

    def test_group_size_missing_replica_groups_uses_default(self):
        assert H.group_size("dimensions={0}", 8) == 8
        assert H.group_size("replica_groups={{0,1,2,3}}", 8) == 4
        assert H.group_size("replica_groups=[2,4]<=[8]", 8) == 4

    def test_rootless_computation_is_an_issue_not_a_crash(self):
        p = H.parse_module_checked(textwrap.dedent("""\
            ENTRY %main (p: f32[4]) -> f32[4] {
              %p = f32[4]{0} parameter(0)
              %x = f32[4]{0} add(%p, %p)
            }
            """))
        assert [i.code for i in p.issues] == ["no-root"]
        assert "main" in p.comps and p.entry == "main"

    def test_unterminated_and_no_entry(self):
        p = H.parse_module_checked(
            "%aux (p: f32[4]) -> f32[4] {\n"
            "  %p = f32[4]{0} parameter(0)\n"
            "  ROOT %x = f32[4]{0} add(%p, %p)\n")
        codes = {i.code for i in p.issues}
        assert codes == {"unterminated", "no-entry"}
        assert p.comps["aux"].ops

    def test_undefined_operand_flagged(self):
        p = H.parse_module_checked(textwrap.dedent("""\
            ENTRY %main (p: f32[4]) -> f32[4] {
              %p = f32[4]{0} parameter(0)
              ROOT %x = f32[4]{0} add(%p, %ghost)
            }
            """))
        assert [i.code for i in p.issues] == ["undefined-operand"]

    def test_io_aliases_with_nested_braces(self):
        hdr = ("HloModule jit_step, is_scheduled=true, input_output_alias="
               "{ {0}: (0, {}, may-alias), {1}: (3, {}, may-alias) }, "
               "entry_computation_layout={(f32[4]{0})->(f32[4]{0})}\n\n"
               "ENTRY %main (p: f32[4]) -> f32[4] {\n"
               "  ROOT %p = f32[4]{0} parameter(0)\n}\n")
        aliases = H.module_io_aliases(hdr)
        assert [(a.output_index, a.param_number) for a in aliases] == [
            ((0,), 0), ((1,), 3)]
        assert all(a.kind == "may-alias" for a in aliases)

    def test_parse_findings_surface_on_artifacts(self):
        art = _art("ENTRY %main (p: f32[4]) -> f32[4] {\n"
                   "  %p = f32[4]{0} parameter(0)\n")
        fs = art.parse_findings("sharding")
        assert {f.code for f in fs} == {"hlo-parse-unterminated",
                                        "hlo-parse-no-root"}
        assert all(f.severity is Severity.WARNING for f in fs)


# ---------------------------------------------------------------------------
# findings / report
# ---------------------------------------------------------------------------

class TestFindings:
    def test_report_ranks_errors_first_and_counts(self):
        fs = [Finding("a", Severity.INFO, "i", "m"),
              Finding("b", Severity.ERROR, "e", "m"),
              Finding("c", Severity.WARNING, "w", "m")]
        r = report_dict(fs, ["x"], ["a", "b", "c"])
        assert [f["severity"] for f in r["findings"]] == [
            "error", "warning", "info"]
        assert r["counts"]["error"] == 1 and not r["ok"]
        assert r["version"] == 1

    def test_allowlist_downgrades_matching_only(self):
        fs = [Finding("memory", Severity.ERROR, "full-bucket-fp32", "abc"),
              Finding("memory", Severity.ERROR, "full-slot-stripe", "abc")]
        out = apply_allowlist(fs, [{"pass": "memory",
                                    "code": "full-bucket-fp32"}])
        assert out[0].severity is Severity.ALLOWLISTED
        assert out[1].severity is Severity.ERROR

    def test_empty_allowlist_entry_matches_nothing(self):
        fs = [Finding("memory", Severity.ERROR, "x", "m")]
        assert apply_allowlist(fs, [{}])[0].severity is Severity.ERROR


# ---------------------------------------------------------------------------
# framework
# ---------------------------------------------------------------------------

class TestFramework:
    def test_combo_validation(self):
        with pytest.raises(ValueError):
            Combo("rmnp", "fp16")
        with pytest.raises(ValueError):
            Combo("rmnp", "fp32", 0)
        assert Combo("rmnp", "int8-ef", 4).id == "rmnp/int8-ef/accum4"
        assert Combo("rmnp", "fp32", guard=True).id == "rmnp/fp32/accum1/guard"

    def test_catalog_has_all_six_passes(self):
        names = {e["name"] for e in pass_catalog()}
        assert names == {"memory", "sharding", "donation", "overlap",
                         "kernel-lint", "conventions"}

    def test_unknown_pass_raises(self):
        """Asking the runner for a pass nobody registered is an error that
        lists what is registered, not an empty (green) report."""
        art = _art(GOOD_ZERO2, buckets=(BUCKET,))
        with pytest.raises(ValueError, match="unknown pass") as ei:
            run_passes([art], only=["memory", "no-such-pass"])
        assert "no-such-pass" in str(ei.value)
        assert "memory" in str(ei.value)


# ---------------------------------------------------------------------------
# sharding pass
# ---------------------------------------------------------------------------

class TestShardingPass:
    def test_single_weight_gather_is_clean(self):
        fs = ShardingPass().run(_art(GOOD_ZERO2, buckets=(BUCKET,)))
        assert not _errors(fs)

    def test_replicated_momentum_and_slot_gather_flagged(self):
        fs = ShardingPass().run(_art(BAD_ZERO2, buckets=(BUCKET,)))
        codes = sorted(f.code for f in _errors(fs))
        assert codes == ["slot-stripe-gathered", "state-replicated"]

    def test_classifier_keys(self):
        got = classify_all_gathers(BAD_ZERO2, (BUCKET,))
        assert len(got["64x64"]) == 2
        assert len(got["slot:64x64/nu"]) == 1


# ---------------------------------------------------------------------------
# overlap pass
# ---------------------------------------------------------------------------

class TestOverlapPass:
    def test_independent_chains_no_edges(self):
        rep = collective_overlap_report(GOOD_ZERO2, [("64x64", 64, 64)])
        assert rep["n_serialization_edges"] == 0
        fs = OverlapPass().run(_art(GOOD_ZERO2, buckets=(BUCKET,)))
        assert not _errors(fs)

    def test_gather_feeding_collective_through_while_body(self):
        # bucket A's update gather feeds the while loop whose body runs
        # bucket B's reduce-scatter: a serialization edge across the call
        # boundary that a single-computation scan would miss
        hlo = textwrap.dedent("""\
            ENTRY %main (p0: f32[1,64,64]) -> (s32[], f32[4,64,64]) {
              %p0 = f32[1,64,64]{2,1,0} parameter(0)
              %upd = f32[1,64,64]{2,1,0} add(%p0, %p0)
              %ag = f32[4,64,64]{2,1,0} all-gather(%upd), replica_groups={{0,1,2,3}}, dimensions={0}
              %z = s32[] constant(0)
              %init = (s32[], f32[4,64,64]{2,1,0}) tuple(%z, %ag)
              ROOT %w = (s32[], f32[4,64,64]{2,1,0}) while(%init), condition=%cond, body=%body
            }

            %cond (arg: (s32[], f32[4,64,64])) -> pred[] {
              %arg = (s32[], f32[4,64,64]{2,1,0}) parameter(0)
              %i = s32[] get-tuple-element(%arg), index=0
              %c = s32[] constant(2)
              ROOT %lt = pred[] compare(%i, %c), direction=LT
            }

            %body (arg: (s32[], f32[4,64,64])) -> (s32[], f32[4,64,64]) {
              %arg = (s32[], f32[4,64,64]{2,1,0}) parameter(0)
              %i = s32[] get-tuple-element(%arg), index=0
              %x = f32[4,64,64]{2,1,0} get-tuple-element(%arg), index=1
              %sl = f32[1,64,64]{2,1,0} slice(%x), slice={[0:1], [0:64], [0:64]}
              %rs = f32[1,64,64] reduce-scatter(%sl), replica_groups={{0,1,2,3}}, to_apply=%add
              %x2 = f32[4,64,64]{2,1,0} all-gather(%rs), replica_groups={{0,1,2,3}}, dimensions={0}
              %one = s32[] constant(1)
              %i2 = s32[] add(%i, %one)
              ROOT %t = (s32[], f32[4,64,64]{2,1,0}) tuple(%i2, %x2)
            }

            %add (a: f32[], b: f32[]) -> f32[] {
              %a = f32[] parameter(0)
              %b = f32[] parameter(1)
              ROOT %s = f32[] add(%a, %b)
            }
            """)
        rep = collective_overlap_report(hlo, [("64x64", 64, 64)])
        assert rep["n_serialization_edges"] >= 1
        assert any(c == "rs" for _u, c, _bu, _bc in
                   rep["serialization_edges"])
        fs = OverlapPass().run(_art(hlo, buckets=(BUCKET,)))
        assert "serialization-edge" in {f.code for f in _errors(fs)}

    def test_missing_weight_gather_is_an_error(self):
        hlo = textwrap.dedent("""\
            ENTRY %main (p0: f32[1,64,64]) -> f32[1,64,64] {
              %p0 = f32[1,64,64]{2,1,0} parameter(0)
              ROOT %upd = f32[1,64,64]{2,1,0} add(%p0, %p0)
            }
            """)
        fs = OverlapPass().run(_art(hlo, buckets=(BUCKET,)))
        assert "no-update-gathers" in {f.code for f in _errors(fs)}


# ---------------------------------------------------------------------------
# donation pass
# ---------------------------------------------------------------------------

class TestDonationPass:
    BIG = DonatedLeaf(0, "params/w", (512, 1024), "float32")   # 2 MiB
    SMALL = DonatedLeaf(1, "opt_state/step", (1,), "float32")

    @staticmethod
    def _hlo(alias_entries, body_extra=""):
        alias = (f", input_output_alias={{ {alias_entries} }}"
                 if alias_entries else "")
        return (
            f"HloModule jit_step, is_scheduled=true{alias}, "
            f"entry_computation_layout="
            f"{{(f32[512,1024]{{1,0}})->(f32[512,1024]{{1,0}})}}\n\n"
            f"ENTRY %main (p0: f32[512,1024], p1: f32[1]) "
            f"-> f32[512,1024] {{\n"
            f"  %p0 = f32[512,1024]{{1,0}} parameter(0)\n"
            f"  %p1 = f32[1]{{0}} parameter(1)\n"
            f"{body_extra}"
            f"  ROOT %o = f32[512,1024]{{1,0}} add(%p0, %p0)\n}}\n")

    def test_all_aliased_is_clean(self):
        hlo = self._hlo("{0}: (0, {}, may-alias), {1}: (1, {}, may-alias)")
        fs = DonationPass().run(_art(hlo, donated=(self.BIG, self.SMALL)))
        assert not _errors(fs)

    def test_dropped_big_leaf_is_error_small_is_warning(self):
        hlo = self._hlo("{1}: (1, {}, may-alias)")
        fs = DonationPass().run(_art(hlo, donated=(self.BIG, self.SMALL)))
        assert [f.code for f in _errors(fs)] == ["donation-dropped"]
        assert _errors(fs)[0].location == "params/w"
        hlo = self._hlo("{0}: (0, {}, may-alias)")
        fs = DonationPass().run(_art(hlo, donated=(self.BIG, self.SMALL)))
        assert not _errors(fs)
        assert any(f.code == "donation-dropped"
                   and f.severity is Severity.WARNING for f in fs)

    def test_no_alias_table_at_all(self):
        fs = DonationPass().run(_art(self._hlo(""),
                                     donated=(self.BIG, self.SMALL)))
        assert [f.code for f in _errors(fs)] == ["no-alias-table"]

    def test_defensive_copy_of_aliased_big_leaf_warns(self):
        hlo = self._hlo(
            "{0}: (0, {}, may-alias), {1}: (1, {}, may-alias)",
            body_extra="  %cp = f32[512,1024]{1,0} copy(%p0)\n")
        fs = DonationPass().run(_art(hlo, donated=(self.BIG, self.SMALL)))
        assert not _errors(fs)
        assert any(f.code == "defensive-copy" for f in fs)


# ---------------------------------------------------------------------------
# memory pass (real jaxprs, single device)
# ---------------------------------------------------------------------------

class TestMemoryPass:
    def test_full_bucket_intermediate_flagged(self):
        import jax
        import jax.numpy as jnp

        def bad(shard):                      # (1,64,64) shard in...
            full = jnp.tile(shard, (4, 1, 1))   # ...full bucket out
            return jnp.sum(full * 2.0)

        jaxpr = jax.make_jaxpr(bad)(
            jax.ShapeDtypeStruct((1, 64, 64), jnp.float32))
        hits = count_jaxpr_buffers(jaxpr, (4, 64, 64), "float32")
        assert hits
        fs = MemoryPass().run(_art(GOOD_ZERO2, buckets=(BUCKET,),
                                   jaxpr=jaxpr))
        assert {f.code for f in _errors(fs)} == {"full-bucket-fp32"}

    def test_sharded_math_and_excluded_gather_clean(self):
        import jax
        import jax.numpy as jnp

        def good(shard):
            upd = shard * 2.0 + 1.0          # stays (1,64,64)
            return jnp.reshape(jnp.broadcast_to(upd, (4, 64, 64)),
                               (4, 64, 64))  # reshape is excluded

        jaxpr = jax.make_jaxpr(good)(
            jax.ShapeDtypeStruct((1, 64, 64), jnp.float32))
        # broadcast_in_dim DOES produce the full shape -> flagged; drop it
        # via exclude to emulate the all_gather discount, then clean
        hits = count_jaxpr_buffers(
            jaxpr, (4, 64, 64), "float32",
            exclude_prims=frozenset({"broadcast_in_dim", "reshape"}))
        assert hits == []

    def test_full_slot_stripe_flagged(self):
        import jax
        import jax.numpy as jnp

        def bad(nu_shard):                   # (1,1,64) slot shard
            return jnp.tile(nu_shard, (4, 1, 1)) * 2.0

        jaxpr = jax.make_jaxpr(bad)(
            jax.ShapeDtypeStruct((1, 1, 64), jnp.float32))
        fs = MemoryPass().run(_art(GOOD_ZERO2, buckets=(BUCKET,),
                                   jaxpr=jaxpr))
        assert {f.code for f in _errors(fs)} == {"full-slot-stripe"}
        assert _errors(fs)[0].location == "64x64/nu"

    def test_bucket_sized_leaf_skips_bucket(self):
        import jax
        import jax.numpy as jnp

        bucket = BucketMeta(
            key="64x64", d_in=64, d_out=64, size=1, padded=4,
            momentum_dtype="float32", slot_shapes={},
            leaf_shapes=((4, 64, 64),))      # a leaf IS bucket-sized

        def f(x):
            return jnp.tile(x, (4, 1, 1)) * 2.0

        jaxpr = jax.make_jaxpr(f)(
            jax.ShapeDtypeStruct((1, 64, 64), jnp.float32))
        fs = MemoryPass().run(_art(GOOD_ZERO2, buckets=(bucket,),
                                   jaxpr=jaxpr))
        assert not _errors(fs)
        assert any(f.code == "bucket-skipped" for f in fs)


# ---------------------------------------------------------------------------
# kernel introspection + lint
# ---------------------------------------------------------------------------

class TestKernelIntrospection:
    def test_real_kernel_launch_metadata(self):
        import jax.numpy as jnp

        from repro.kernels import introspect, ops

        g = jnp.zeros((2, 64, 256), jnp.float32)
        launches = introspect.collect_kernel_launches(
            lambda: ops.rmnp_bucket_update_apply(g, g, g, 0.1, 0.1,
                                                 beta=0.95))
        assert len(launches) == 1
        ln = launches[0]
        assert ln.grid and all(isinstance(d, int) for d in ln.grid)
        blocks = [b for b in ln.blocks if b.memspace != "smem"]
        assert blocks and all(b.array_shape == (2, 64, 256)
                              for b in blocks)
        for b in blocks:
            assert introspect.block_coverage(ln, b)["covers"]
        # blocks double-buffered at their dtype: the block part of the
        # kernel's own accounting (g, v, w in and v_new, w_new out: five
        # fp32 (64, 256) blocks, no temps; the SMEM scalars are not VMEM)
        from repro.kernels.rmnp_update import stripe_vmem_bytes
        assert ln.name == "rmnp_rownorm_apply"
        assert ln.vmem_block_bytes() == stripe_vmem_bytes(64, 256, [4] * 5, 0)

    def test_gappy_grid_detected(self):
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        from repro.kernels import introspect

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        def launch(x):
            # grid 2 over an 8-row array with 2-row blocks: rows [4,8)
            # never covered
            return pl.pallas_call(
                kernel,
                grid=(2,),
                in_specs=[pl.BlockSpec((2, 16), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((2, 16), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((8, 16), jnp.float32),
                interpret=True)(x)

        import jax
        launches = introspect.collect_kernel_launches(
            launch, jax.ShapeDtypeStruct((8, 16), jnp.float32))
        assert len(launches) == 1
        ln = launches[0]
        cov = introspect.block_coverage(ln, ln.in_blocks[0])
        assert not cov["covers"]
        assert (0, 4, 8) in cov["uncovered"]

    def test_lint_pass_clean_on_repo_kernels(self):
        from repro.analysis.kernel_lint import KernelLintPass

        fs = KernelLintPass().run(None)
        assert not _errors(fs), [(f.code, f.location) for f in _errors(fs)]
        summary = [f for f in fs if f.code == "summary"]
        assert summary and "launches" in summary[0].message


# ---------------------------------------------------------------------------
# conventions pass
# ---------------------------------------------------------------------------

class TestConventions:
    def test_pallas_call_outside_kernels_flagged(self, tmp_path):
        f = tmp_path / "rogue.py"
        f.write_text("import jax.experimental.pallas as pl\n"
                     "out = pl.pallas_call(lambda r: None)\n")
        codes = [c for c, _ln, _m in scan_file(str(f), "train/rogue.py")]
        assert codes == ["pallas-call-outside-kernels"]
        codes = [c for c, _ln, _m in scan_file(str(f), "kernels/ok.py")]
        assert codes == []

    def test_bare_dict_plan_cache_flagged(self, tmp_path):
        f = tmp_path / "eng.py"
        f.write_text("plan_cache = {}\n"
                     "_plans = {k: 1 for k in ()}\n"
                     "other = {}\n")
        codes = [c for c, _ln, _m in scan_file(str(f), "core/eng.py")]
        assert codes == ["bare-dict-plan-cache", "bare-dict-plan-cache"]

    def test_plancache_class_is_clean(self, tmp_path):
        f = tmp_path / "eng.py"
        f.write_text("from repro.core.bucketing import PlanCache\n"
                     "plan_cache = PlanCache()\n")
        assert scan_file(str(f), "core/eng.py") == []

    def test_syntax_error_is_a_finding(self, tmp_path):
        f = tmp_path / "broken.py"
        f.write_text("def f(:\n")
        codes = [c for c, _ln, _m in scan_file(str(f), "core/broken.py")]
        assert codes == ["syntax-error"]

    def test_repo_tree_is_clean(self):
        from repro.analysis.conventions import ConventionsPass

        fs = ConventionsPass().run(None)
        assert not _errors(fs), [f.message for f in _errors(fs)]


# ---------------------------------------------------------------------------
# 4-device registry sweep + deliberately broken variants (subprocess)
# ---------------------------------------------------------------------------

def _worker_env():
    root = Path(__file__).resolve().parents[1]
    return dict(
        os.environ, JAX_PLATFORMS="cpu",
        PYTHONPATH=os.pathsep.join(
            [str(root / "src"), os.environ.get("PYTHONPATH", "")]
        ).rstrip(os.pathsep))


@pytest.mark.skipif(os.environ.get("CI") == "true",
                    reason="CI runs python -m repro.analysis.check --all as "
                           "a dedicated job; the in-suite sweep would "
                           "double it")
def test_registry_sweep_finding_free():
    """Every optimizer x engine lowers and passes every analysis check."""
    worker = Path(__file__).parent / "_analysis_worker.py"
    r = subprocess.run([sys.executable, str(worker), "sweep"],
                       env=_worker_env(), capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert r.stdout.rstrip().endswith("ANALYSIS_SWEEP_OK"), r.stdout


@pytest.mark.skipif(os.environ.get("CI") == "true",
                    reason="CI covers the broken variants via the analysis "
                           "job's fixtures; skip the slow subprocess here")
def test_broken_variants_are_caught():
    """Forced momentum all-gather and dropped donation must be detected
    by the sharding/memory and donation passes on REAL lowered steps."""
    worker = Path(__file__).parent / "_analysis_worker.py"
    r = subprocess.run([sys.executable, str(worker), "broken"],
                       env=_worker_env(), capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert r.stdout.rstrip().endswith("ANALYSIS_BREAK_OK"), r.stdout

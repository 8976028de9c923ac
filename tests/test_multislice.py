"""Multi-slice tier (distributed/multislice, FLAGS_multislice).

Proved on the 8-virtual-device CPU mesh (2 slices x 4 devices):

- ``SliceTopology`` builds the 2-tier mesh with an OUTERMOST ``slice``
  axis (contiguous per-slice device blocks — the stride regression the
  ``extra_axes_position="outer"`` fix exists for), classifies link
  classes, and exposes per-slice local meshes / slice ids;
- ``HierarchicalGradReducer`` (ICI reduce-scatter -> DCN allreduce on
  the 1/ici shard -> ICI all-gather) is BITWISE equal to the naive flat
  per-axis psum baseline, bitwise order-independent across bucket
  partitions, and correct for non-divisible bucket lengths (padding);
- the 2-slice TrainStep dryrun: ``FLAGS_multislice=hierarchical`` has
  bitwise loss AND parameter parity with the flat baseline across
  multiple steps, and tracks the slice-less GSPMD step numerically;
- ``comm_check`` link classes: the hierarchical plan's per-step DCN
  bytes == bucket_bytes / ici_size, C004 fires on the naive
  flat-over-DCN plan and stays silent on the hierarchical one, C005
  flags sub-floor DCN buckets; lint rule J015 flags a DCN-axis
  collective inside a scan body;
- the tooling: ``tools/lint_graph.py --model multislice`` is error-free
  and the ``--matrix`` sweep carries the ``multislice`` dimension.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.analysis import comm_check, jaxpr_lint, plan_check
from paddle_tpu.core import flags as core_flags
from paddle_tpu.distributed import overlap
from paddle_tpu.distributed.multislice import (HierarchicalGradReducer,
                                               SliceTopology)
from paddle_tpu.distributed.topology import (AXIS_ORDER,
                                             CommunicateTopology,
                                             create_hybrid_mesh,
                                             set_hybrid_mesh)
from paddle_tpu.framework.functional import functional_call
from paddle_tpu.framework.sharded import make_sharded_train_step
from paddle_tpu.optimizer import AdamW
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM


def rules_of(diags):
    return {d.rule for d in diags}


def jitted(fn, *args):
    return jax.jit(fn)(*args)


@pytest.fixture
def ms_flags():
    prev = core_flags.get_flags(["multislice", "multislice_dcn_bucket_mb"])
    yield
    core_flags.set_flags(prev)


# ---------------------------------------------------------------------------
# Topology: outer extra-axes placement + helpers
# ---------------------------------------------------------------------------

class TestTopology:
    def test_outer_placement_contiguous_slice_blocks(self):
        """The satellite fix: extra_axes used to append after mp
        (innermost) — a slice axis there would stripe cross-slice (DCN)
        traffic onto ICI-adjacent device strides. Outer placement makes
        each slice a contiguous block of the enumeration."""
        devs = jax.devices()
        mesh = create_hybrid_mesh(dp=4, extra_axes={"slice": 2},
                                  extra_axes_position="outer")
        assert mesh.axis_names[0] == "slice"
        assert mesh.axis_names[1:] == AXIS_ORDER
        blocks = mesh.devices.reshape(2, -1)
        assert list(blocks[0]) == devs[:4]
        assert list(blocks[1]) == devs[4:]

    def test_inner_placement_unchanged_default(self):
        """Default stays the historical innermost append (an extra
        high-bandwidth axis like ep wants ICI adjacency)."""
        devs = jax.devices()
        mesh = create_hybrid_mesh(dp=4, extra_axes={"slice": 2})
        assert mesh.axis_names[-1] == "slice"
        # innermost: the slice axis strides by 1 — slice 1's first
        # device is devices[1], NOT devices[4]
        flat = mesh.devices.reshape(4, 2)
        assert flat[0][1] == devs[1]

    def test_bad_position_rejected(self):
        with pytest.raises(ValueError, match="extra_axes_position"):
            create_hybrid_mesh(dp=4, extra_axes={"slice": 2},
                               extra_axes_position="sideways")

    def test_degree_inference_with_extra_axes(self):
        """-1 inference composes with extra axes in both positions."""
        for pos in ("outer", "inner"):
            mesh = create_hybrid_mesh(dp=-1, extra_axes={"slice": 2},
                                      extra_axes_position=pos)
            assert mesh.shape["dp"] == jax.device_count() // 2
            assert mesh.shape["slice"] == 2

    def test_communicate_topology_round_trip_two_slice(self):
        dims = (2, 1, 4, 1, 1, 1)
        topo = CommunicateTopology(("slice",) + AXIS_ORDER, dims)
        assert topo.world_size() == 8
        for rank in range(topo.world_size()):
            coord = topo.get_coord(rank)
            kw = dict(zip(("slice",) + AXIS_ORDER, coord))
            assert topo.get_rank(**kw) == rank
        # the slice axis groups are the two contiguous halves
        assert topo.get_axis_list("slice", 0) == list(range(4))
        assert topo.get_axis_list("slice", 1) == list(range(4, 8))

    def test_slice_topology_invariants(self):
        topo = SliceTopology(2, dp=4)
        assert topo.num_slices == 2
        assert topo.ici_size == 4
        assert topo.link_class("slice") == "dcn"
        assert topo.link_class("dp") == "ici"
        assert topo.dcn_axes() == ["slice"]
        assert "dp" in topo.ici_axes()
        with pytest.raises(KeyError):
            topo.link_class("nonexistent")
        devs = jax.devices()
        for i, d in enumerate(devs):
            assert topo.slice_id(d) == i // 4
        for s in range(2):
            local = topo.local_mesh(s)
            assert "slice" not in local.axis_names
            assert list(local.devices.ravel()) == topo.slice_devices(s)
            assert topo.slice_devices(s) == devs[s * 4:(s + 1) * 4]
        assert "slice" in comm_check.dcn_axes()

    def test_slice_axis_name_collision_rejected(self):
        with pytest.raises(ValueError, match="collides"):
            SliceTopology(2, dp=4, slice_axis="dp")


# ---------------------------------------------------------------------------
# The hierarchical reducer
# ---------------------------------------------------------------------------

def _grads(seed=0, sizes=((13,), (4, 7), (65,), (3, 3, 3), (31,))):
    """Deliberately awkward sizes: none of the flat bucket lengths is
    guaranteed divisible by the ICI degree."""
    rng = np.random.default_rng(seed)
    return {f"g{i}": jnp.asarray(rng.standard_normal(s) * 100,
                                 jnp.float32)
            for i, s in enumerate(sizes)}


def _slice_mesh():
    return SliceTopology(2, dp=4).mesh


def _reduce_on_mesh(mesh, grads, body):
    """Run `body(named_grads) -> named_grads` inside a shard_map over
    {slice, dp} with every device holding DISTINCT grad values (so the
    reduction order is observable bitwise)."""
    names = list(grads)

    def fn(ranks, *gs):
        # de-correlate per device: each rank contributes rank-dependent
        # values, the reduction must combine all 8
        r = (ranks[0].astype(jnp.float32) + 1.0)
        named = {n: g * r for n, g in zip(names, gs)}
        out = body(named)
        return tuple(out[n] for n in names)

    ranks = jnp.arange(8, dtype=jnp.int32)
    specs = tuple(P() for _ in names)
    fn_m = overlap.shard_map_compat(
        fn, mesh, (P(("slice", "dp")),) + specs, specs, ("slice", "dp"))
    return dict(zip(names, jitted(fn_m, ranks, *grads.values())))


class TestHierarchicalReducer:
    def test_hierarchical_bitwise_equals_flat(self, ms_flags):
        mesh = _slice_mesh()
        grads = _grads()
        r = HierarchicalGradReducer(axis="dp", dcn_axis="slice",
                                    bucket_bytes=256)
        hier = _reduce_on_mesh(
            mesh, grads, lambda g: r.reduce_in_axes(g, "hierarchical"))
        flat = _reduce_on_mesh(
            mesh, grads, lambda g: r.reduce_in_axes(g, "flat"))
        for n in grads:
            assert np.array_equal(np.asarray(hier[n]), np.asarray(flat[n])
                                  ), n

    @pytest.mark.parametrize("bucket_bytes", [1, 300, 1 << 30])
    def test_bucket_partition_independence_bitwise(self, bucket_bytes,
                                                   ms_flags):
        """Bucket permutations/partitions cannot change any element's
        reduction order — bitwise invariant, including the padding path
        (every awkward bucket length exercises it)."""
        mesh = _slice_mesh()
        grads = _grads(seed=3)
        ref = _reduce_on_mesh(
            mesh, grads,
            lambda g: HierarchicalGradReducer(
                axis="dp", dcn_axis="slice",
                bucket_bytes=1 << 20).reduce_in_axes(g))
        got = _reduce_on_mesh(
            mesh, grads,
            lambda g: HierarchicalGradReducer(
                axis="dp", dcn_axis="slice",
                bucket_bytes=bucket_bytes).reduce_in_axes(g))
        for n in grads:
            assert np.array_equal(np.asarray(got[n]), np.asarray(ref[n]))
        # permuted parameter order: same values per name
        perm = dict(reversed(list(grads.items())))
        got_p = _reduce_on_mesh(
            mesh, perm,
            lambda g: HierarchicalGradReducer(
                axis="dp", dcn_axis="slice",
                bucket_bytes=300).reduce_in_axes(g))
        for n in grads:
            assert np.array_equal(np.asarray(got_p[n]), np.asarray(ref[n]))

    def test_values_match_per_axis_psum_reference(self, ms_flags):
        """The hierarchical result == psum over dp then slice, per
        parameter (the association both modes share)."""
        mesh = _slice_mesh()
        grads = _grads(seed=7)
        hier = _reduce_on_mesh(
            mesh, grads,
            lambda g: HierarchicalGradReducer(
                axis="dp", dcn_axis="slice",
                bucket_bytes=128).reduce_in_axes(g))
        ref = _reduce_on_mesh(
            mesh, grads,
            lambda g: {n: lax.psum(lax.psum(v, "dp"), "slice")
                       for n, v in g.items()})
        for n in grads:
            assert np.array_equal(np.asarray(hier[n]), np.asarray(ref[n]))

    def test_default_bucket_from_dcn_flag(self, ms_flags):
        assert int(core_flags.flag("multislice_dcn_bucket_mb")) > \
            int(core_flags.flag("comm_overlap_bucket_mb")), \
            "DCN buckets must default larger than the ICI bucket class"
        core_flags.set_flags({"multislice_dcn_bucket_mb": 7})
        assert HierarchicalGradReducer().bucket_bytes == 7 << 20

    def test_bad_mode_rejected(self):
        r = HierarchicalGradReducer(bucket_bytes=1)
        with pytest.raises(ValueError, match="mode"):
            r.reduce_in_axes({"g": jnp.ones(3)}, mode="diagonal")

    def test_dcn_bytes_accounting(self):
        """Acceptance: per-step DCN bytes == bucket_bytes / ici_size for
        the hierarchical plan, == full bucket for the flat plan."""
        r = HierarchicalGradReducer(bucket_bytes=1 << 30)
        grads = {"g": np.zeros((1024,), np.float32)}  # one 4 KiB bucket
        assert r.dcn_bytes_per_step(grads, ici_size=4, dcn_size=2) == 1024
        assert r.dcn_bytes_per_step(grads, ici_size=4, dcn_size=2,
                                    mode="flat") == 4096
        plan = r.hop_plan(grads, 4, 2)
        assert [s.link for s in plan] == ["ici", "dcn", "ici"]
        assert [s.name for s in plan] == [
            "slice_reduce_scatter", "dcn_allreduce", "slice_all_gather"]


# ---------------------------------------------------------------------------
# Satellite: BucketedGradReducer reduce_scatter padding fix
# ---------------------------------------------------------------------------

class TestReduceScatterPadding:
    @pytest.mark.parametrize("sizes", [((13,),), ((5,), (9, 3), (2,))])
    def test_non_divisible_bucket_bitwise_vs_psum(self, sizes):
        """The satellite bug: psum_scatter(tiled=True) requires the flat
        bucket length to divide the axis size; bucketize produces
        arbitrary lengths (13, 32+5... none divisible by 8). The padded
        path must return values bitwise equal to a plain psum."""
        mesh = create_hybrid_mesh(dp=8)
        grads = _grads(seed=11, sizes=sizes)
        names = list(grads)

        def run(op):
            def fn(*gs):
                named = dict(zip(names, gs))
                out = overlap.BucketedGradReducer(
                    axis="dp", bucket_bytes=1 << 30).reduce_in_axis(
                        named, op=op)
                return tuple(out[n] for n in names)
            specs = tuple(P() for _ in names)
            return jitted(overlap.shard_map_compat(
                fn, mesh, specs, specs, {"dp"}), *grads.values())

        rs = run("reduce_scatter")
        ar = run("all_reduce")
        for got, want in zip(rs, ar):
            assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# The 2-slice TrainStep dryrun
# ---------------------------------------------------------------------------

def _gpt_cfg(**kw):
    base = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                max_position_embeddings=32, hidden_dropout=0.0,
                attention_dropout=0.0, use_flash_attention=False)
    base.update(kw)
    return GPTConfig(**base)


def _gpt_loss(m, p, b):
    ids, labels = b
    return functional_call(m, p, ids, labels, training=True)


def _train(mesh, mode, batches, fsdp_axis=None):
    core_flags.set_flags({"multislice": mode})
    set_hybrid_mesh(mesh)
    paddle.seed(0)
    ts = make_sharded_train_step(GPTForCausalLM(_gpt_cfg()), AdamW(1e-3),
                                 _gpt_loss, mesh=mesh,
                                 fsdp_axis=fsdp_axis)
    losses = [float(ts.step(b)) for b in batches]
    set_hybrid_mesh(None)
    return losses, ts


def _batches(n=3, batch=8, seq=16, vocab=64, seed=0):
    rng = np.random.default_rng(seed)
    return [(jnp.asarray(rng.integers(0, vocab, (batch, seq)),
                         jnp.int32),) * 2 for _ in range(n)]


class TestMultisliceTrainStep:
    def test_two_slice_dryrun_bitwise_parity(self, ms_flags):
        """THE acceptance dryrun: hierarchical TrainStep loss AND updated
        params bitwise == the flat single-axis-psum-per-link baseline,
        over 3 real GPT steps on the 2-slice x 4-device CPU mesh."""
        topo = SliceTopology(2, dp=4)
        batches = _batches()
        loss_f, ts_f = _train(topo.mesh, "flat", batches)
        loss_h, ts_h = _train(topo.mesh, "hierarchical", batches)
        assert loss_h == loss_f, (loss_h, loss_f)
        for n in ts_f.params:
            assert np.array_equal(np.asarray(ts_f.params[n]),
                                  np.asarray(ts_h.params[n])), n

    def test_tracks_gspmd_single_mesh_step(self, ms_flags):
        """Semantic anchor: the explicit 2-tier reduction tracks the
        slice-less GSPMD dp=8 step numerically (different float
        association — tolerance, not bitwise)."""
        topo = SliceTopology(2, dp=4)
        batches = _batches()
        loss_h, _ = _train(topo.mesh, "hierarchical", batches)
        core_flags.set_flags({"multislice": "off"})
        mesh = create_hybrid_mesh(dp=8)
        loss_g, _ = _train(mesh, "off", batches)
        np.testing.assert_allclose(loss_h, loss_g, rtol=2e-5, atol=2e-5)

    def test_inert_without_slice_axis(self, ms_flags):
        """FLAGS_multislice=hierarchical on a slice-less mesh must leave
        the step byte-identical to off (the matrix gate relies on it)."""
        mesh = create_hybrid_mesh(dp=8)
        batches = _batches(n=2)
        loss_off, _ = _train(mesh, "off", batches)
        loss_on, ts = _train(mesh, "hierarchical", batches)
        assert loss_on == loss_off
        assert ts._multislice is None
        assert ts.plan.flags["multislice"] == "off"

    def test_fsdp_composition_rejected(self, ms_flags):
        topo = SliceTopology(2, dp=2, sharding=2)
        core_flags.set_flags({"multislice": "hierarchical"})
        set_hybrid_mesh(topo.mesh)
        paddle.seed(0)
        with pytest.raises(ValueError, match="fsdp"):
            make_sharded_train_step(GPTForCausalLM(_gpt_cfg()),
                                    AdamW(1e-3), _gpt_loss,
                                    mesh=topo.mesh)
        set_hybrid_mesh(None)

    def test_plan_declares_and_trace_verifies(self, ms_flags):
        """The composed step passes the S/D plan rules; the recorded hop
        plan carries the three hierarchical stages with the DCN payload
        equal to the 1/ici shard (C004 silent); the flat arm's DCN stage
        carries the full bucket (C004 fires)."""
        topo = SliceTopology(2, dp=4)
        batches = _batches(n=1)
        for mode, c004_expected in (("hierarchical", False), ("flat",
                                                              True)):
            core_flags.set_flags({"multislice": mode})
            set_hybrid_mesh(topo.mesh)
            paddle.seed(0)
            ts = make_sharded_train_step(
                GPTForCausalLM(_gpt_cfg()), AdamW(1e-3), _gpt_loss,
                mesh=topo.mesh, fsdp_axis=None)
            closed, donate = ts.trace_step(batches[0])
            diags = plan_check.check_plan(ts.plan, closed,
                                          donate_argnums=donate)
            assert [d for d in diags if d.severity == "error"] == [], \
                [d.format() for d in diags]
            assert ts.plan.flags["multislice"] == mode
            node_names = [n.name for n in ts.plan.nodes]
            assert "multislice_local_grads" in node_names
            dcn = [s for _, s in ts.plan.comm_specs if s.link == "dcn"]
            ici = [s for _, s in ts.plan.comm_specs if s.link == "ici"]
            assert dcn and ici
            c004 = [d for s in dcn
                    for d in comm_check.check_comm_spec(s)
                    if d.rule == "C004"]
            assert bool(c004) == c004_expected, mode
            if mode == "hierarchical":
                assert {n.name for n in ts.plan.nodes} >= {
                    "multislice_reduce_scatter[ici]",
                    "multislice_allreduce[dcn]",
                    "multislice_all_gather[ici]"}
                bucket = sum(int(v.size) * v.dtype.itemsize
                             for v in ts.params.values())
                assert sum(s.payload_bytes for s in dcn) == \
                    -(-bucket // 4), \
                    "per-step DCN bytes must be bucket_bytes/ici_size"
            set_hybrid_mesh(None)

    def test_step_lints_clean_of_new_rules(self, ms_flags):
        """The hierarchical step's own graph must not trip J015 (no DCN
        collective in a loop body) nor J014's out-of-jit shape."""
        topo = SliceTopology(2, dp=4)
        core_flags.set_flags({"multislice": "hierarchical"})
        set_hybrid_mesh(topo.mesh)
        paddle.seed(0)
        ts = make_sharded_train_step(GPTForCausalLM(_gpt_cfg()),
                                     AdamW(1e-3), _gpt_loss,
                                     mesh=topo.mesh, fsdp_axis=None)
        closed, donate = ts.trace_step(_batches(n=1)[0])
        diags = jaxpr_lint.lint_jaxpr(closed, donate_argnums=donate)
        assert "J015" not in rules_of(diags)
        assert [d for d in diags if d.severity == "error"] == [], \
            [d.format() for d in diags]
        set_hybrid_mesh(None)


# ---------------------------------------------------------------------------
# comm_check link classes: C004 / C005
# ---------------------------------------------------------------------------

class TestLinkClassRules:
    def test_c004_fires_on_flat_over_dcn(self):
        bucket = 100 << 20
        naive = comm_check.spec_for_dcn_allreduce(
            bucket, 2, reduced_from_bytes=bucket, ici_size=64)
        assert "C004" in rules_of(comm_check.check_comm_spec(naive))

    def test_c004_silent_on_hierarchical_shard(self):
        bucket = 100 << 20
        good = comm_check.spec_for_dcn_allreduce(
            bucket // 64, 2, reduced_from_bytes=bucket, ici_size=64)
        assert "C004" not in rules_of(comm_check.check_comm_spec(good))

    def test_c004_needs_upstream_ici(self):
        """A single-slice-of-1-chip job (ici_size=1) has no shard to
        send — the full payload IS minimal; C004 must stay silent."""
        spec = comm_check.spec_for_dcn_allreduce(
            1 << 20, 2, reduced_from_bytes=1 << 20, ici_size=1)
        assert "C004" not in rules_of(comm_check.check_comm_spec(spec))

    def test_c005_dcn_latency_floor(self):
        small = comm_check.spec_for_dcn_allreduce(
            64 * 1024, 2, reduced_from_bytes=64 * 1024 * 4, ici_size=4)
        assert "C005" in rules_of(comm_check.check_comm_spec(small))
        big = comm_check.spec_for_dcn_allreduce(
            4 << 20, 2, reduced_from_bytes=(4 << 20) * 4, ici_size=4)
        assert "C005" not in rules_of(comm_check.check_comm_spec(big))

    def test_c002_is_ici_only(self):
        """The ICI latency floor must not double-report on DCN specs
        (C005 owns that link class)."""
        small = comm_check.spec_for_dcn_allreduce(
            8 * 1024, 2, reduced_from_bytes=32 * 1024, ici_size=4)
        rules = rules_of(comm_check.check_comm_spec(small))
        assert "C002" not in rules
        assert "C005" in rules

    def test_dcn_axis_registry(self):
        assert "slice" in comm_check.dcn_axes()
        comm_check.register_dcn_axis("slice_b")
        assert comm_check.link_class("slice_b") == "dcn"
        assert comm_check.link_class("dp") == "ici"
        comm_check._DCN_AXES.discard("slice_b")

    def test_production_bucket_clears_floors(self):
        """The default FLAGS_multislice_dcn_bucket_mb at a v5e-256-class
        slice (ici=64): every hierarchical stage is floor-clean."""
        bucket = int(core_flags.flag("multislice_dcn_bucket_mb")) << 20
        for spec in (
                comm_check.spec_for_slice_reduce_scatter(bucket, 64),
                comm_check.spec_for_dcn_allreduce(
                    bucket // 64, 2, reduced_from_bytes=bucket,
                    ici_size=64),
                comm_check.spec_for_slice_all_gather(bucket, 64)):
            assert [d for d in comm_check.check_comm_spec(spec)] == [], \
                spec.name


# ---------------------------------------------------------------------------
# J015: DCN collective inside a compiled loop body
# ---------------------------------------------------------------------------

class TestJ015:
    def _lint_loop_body(self, axis):
        mesh = SliceTopology(2, dp=4).mesh

        def fn(x):
            def body(carry, _):
                return carry + lax.psum(x, axis), None
            out, _ = lax.scan(body, jnp.zeros_like(x), None, length=3)
            return out

        sm = overlap.shard_map_compat(
            fn, mesh, (P(("slice", "dp")),), P(("slice", "dp")),
            ("slice", "dp"))
        closed = jax.make_jaxpr(sm)(jnp.arange(8.0))
        return jaxpr_lint.lint_jaxpr(closed, rules=["J015"])

    def test_fires_on_dcn_axis_in_scan(self):
        diags = self._lint_loop_body("slice")
        assert "J015" in rules_of(diags)
        assert any("slice" in d.message for d in diags)

    def test_silent_on_ici_axis_in_scan(self):
        assert self._lint_loop_body("dp") == []

    def test_silent_outside_loops(self):
        mesh = SliceTopology(2, dp=4).mesh
        sm = overlap.shard_map_compat(
            lambda x: lax.psum(x, "slice"), mesh,
            (P(("slice", "dp")),), P(), ("slice", "dp"))
        closed = jax.make_jaxpr(sm)(jnp.arange(8.0))
        assert jaxpr_lint.lint_jaxpr(closed, rules=["J015"]) == []


# ---------------------------------------------------------------------------
# Tooling: lint_graph model + matrix dimension, flags
# ---------------------------------------------------------------------------

class TestTooling:
    def test_multislice_model_in_lint_graph_catalog(self, ms_flags):
        from tools import lint_graph
        assert "multislice" in lint_graph.MODELS
        diags, n_eqns = lint_graph.MODELS["multislice"]()
        assert n_eqns > 0
        errors = [d for d in diags if d.severity == "error"]
        assert errors == [], [d.format() for d in errors]

    def test_matrix_carries_multislice_dimension(self, ms_flags):
        from tools import lint_graph
        names = [n for n, _ in plan_check.TIER_FLAGS]
        assert "multislice" in names
        combos = [c for c in plan_check.iter_tier_combos()
                  if c["comm_overlap"] == "off"
                  and not c["cp_nested_ring"] and not c["pallas_conv"]
                  and c["offload_optimizer"] == "off"
                  and not c["remat"]]
        assert {c["multislice"] for c in combos} == {"off",
                                                     "hierarchical"}
        rc, report = lint_graph._run_matrix_impl(
            min_severity="error", with_dryrun=False, combos=combos)
        assert rc == 0, report
        assert report["errors"] == 0
        assert len(report["combos"]) == len(combos)

    def test_matrix_legacy_combos_still_accepted(self, ms_flags):
        """Pre-multislice combo dicts (no 'multislice' key) must keep
        working — in-process callers pass historical subsets."""
        from tools import lint_graph
        combos = [{"offload_optimizer": "off", "comm_overlap": "off",
                   "cp_nested_ring": False, "pallas_conv": 0,
                   "remat": False}]
        rc, report = lint_graph._run_matrix_impl(
            min_severity="error", with_dryrun=False, combos=combos)
        assert rc == 0

    def test_flags_registered(self):
        assert core_flags.flag("multislice") in ("off", "flat",
                                                 "hierarchical")
        with pytest.raises(ValueError):
            core_flags.set_flags({"multislice": "diagonal"})
        assert int(core_flags.flag("multislice_dcn_bucket_mb")) > \
            int(core_flags.flag("comm_overlap_bucket_mb"))

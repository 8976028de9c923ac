"""Communication-overlap tier (distributed/overlap.py, FLAGS_comm_overlap).

Proved on the 8-virtual-device CPU mesh (conftest provisions it):

- flag off is the *current* step — the SP layer graph with the overlap
  hooks disabled is equation-identical to the pre-overlap GSPMD path;
- decomposed collective matmul (bidirectional ppermute pipelines) matches
  the one-shot collective in values AND grads, and a TP/SP layer stack
  trained under ``tp`` tracks the GSPMD step loss/grads;
- ZeRO-3 gather-ahead (``tp_zero``) keeps multi-step training parity on
  an fsdp-sharded mesh;
- DP bucketed gradient reduction is bucket-order independent (bitwise)
  and equals the per-parameter reduce it replaces;
- the static ICI accounting (C001–C003) and lint rule J014 fire on the
  patterns they document and stay quiet on the disciplined forms;
- the telemetry ``comm`` phase and ``tools/trace_view.py``'s comm
  aggregation see the decomposed traffic.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.analysis import comm_check
from paddle_tpu.analysis.jaxpr_lint import lint_fn
from paddle_tpu.core import flags as core_flags
from paddle_tpu.distributed import overlap
from paddle_tpu.distributed.fleet.layers.mpu import mp_layers
from paddle_tpu.distributed.fleet.utils.sequence_parallel_utils import (
    ColumnSequenceParallelLinear, RowSequenceParallelLinear,
    sequence_parallel_constraint)
from paddle_tpu.distributed.topology import (create_hybrid_mesh,
                                             set_hybrid_mesh)
from paddle_tpu.framework.functional import functional_call, get_params
from paddle_tpu.framework.sharded import make_sharded_train_step
from paddle_tpu.optimizer import AdamW


def rules_of(diags):
    return {d.rule for d in diags}


def jitted(fn, *args):
    """Dispatch through jit, as every production call site does (they
    all live inside the jitted step)."""
    return jax.jit(fn)(*args)


@pytest.fixture
def overlap_flag():
    """Restore every comm-overlap flag afterwards."""
    prev = core_flags.get_flags(["comm_overlap", "comm_overlap_chunks",
                                 "comm_overlap_bucket_mb"])
    yield
    core_flags.set_flags(prev)
    set_hybrid_mesh(None)


@pytest.fixture
def mp8_mesh():
    mesh = create_hybrid_mesh(mp=8)
    set_hybrid_mesh(mesh)
    yield mesh
    set_hybrid_mesh(None)


# ---------------------------------------------------------------------------
# Decomposed collective matmul: values + grads vs the one-shot collective
# ---------------------------------------------------------------------------

class TestDecomposedMatmul:

    def _data(self, b=2, s=16, k=12, m=24, seed=0):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((b, s, k)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((k, m)), jnp.float32)
        bias = jnp.asarray(rng.standard_normal((m,)), jnp.float32)
        return x, w, bias

    @pytest.mark.parametrize("chunks", [1, 2])
    @pytest.mark.parametrize("with_bias", [False, True])
    def test_allgather_matmul_values(self, mp8_mesh, chunks, with_bias):
        x, w, bias = self._data()
        b = bias if with_bias else None
        y = jitted(lambda x, w: overlap.allgather_matmul(
            x, w, b, mesh=mp8_mesh, chunks=chunks), x, w)
        ref = x @ w + (bias if with_bias else 0.0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("chunks", [1, 2])
    @pytest.mark.parametrize("with_bias", [False, True])
    def test_matmul_reduce_scatter_values(self, mp8_mesh, chunks,
                                          with_bias):
        x, w, bias = self._data(k=16)
        b = bias if with_bias else None
        y = jitted(lambda x, w: overlap.matmul_reduce_scatter(
            x, w, b, mesh=mp8_mesh, chunks=chunks), x, w)
        ref = x @ w + (bias if with_bias else 0.0)
        # the travelling accumulators reassociate the K-reduction
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_grads_match_reference(self, mp8_mesh):
        x, w1, _ = self._data(k=12, m=24)
        rng = np.random.default_rng(1)
        w2 = jnp.asarray(rng.standard_normal((24, 12)), jnp.float32)

        def loss_dec(x, w1, w2):
            h = overlap.allgather_matmul(x, w1, mesh=mp8_mesh, chunks=1)
            h = jax.nn.gelu(h)
            return jnp.sum(overlap.matmul_reduce_scatter(
                h, w2, mesh=mp8_mesh, chunks=1) ** 2)

        gd = jitted(jax.grad(loss_dec, argnums=(1, 2)), x, w1, w2)
        gr = jax.grad(lambda x, a, b: jnp.sum(
            (jax.nn.gelu(x @ a) @ b) ** 2), argnums=(1, 2))(x, w1, w2)
        for got, want in zip(gd, gr):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-4)

    def test_shape_validation(self, mp8_mesh):
        x = jnp.zeros((2, 15, 8), jnp.float32)  # 15 % 8 != 0
        w = jnp.zeros((8, 16), jnp.float32)
        with pytest.raises(ValueError):
            overlap.allgather_matmul(x, w, mesh=mp8_mesh)
        with pytest.raises(ValueError):
            overlap.matmul_reduce_scatter(x, w, mesh=mp8_mesh)

    def test_can_decompose_gates(self, mp8_mesh):
        assert overlap.can_decompose(mp8_mesh, "mp")
        assert not overlap.can_decompose(mp8_mesh, "dp")   # size 1
        assert not overlap.can_decompose(None, "mp")
        dp_mesh = create_hybrid_mesh(dp=8)
        assert not overlap.can_decompose(dp_mesh, "mp")


# ---------------------------------------------------------------------------
# Flag off == the current (pre-overlap) step, equation for equation
# ---------------------------------------------------------------------------

class TestFlagOff:

    def _sp_layer_jaxpr(self):
        paddle.seed(0)
        layer = ColumnSequenceParallelLinear(16, 32, gather_output=False)
        x = jnp.zeros((2, 16, 16), jnp.float32)
        params = get_params(layer)
        return str(jax.make_jaxpr(
            lambda p, x: functional_call(layer, p, x))(params, x))

    def test_off_graph_identical_to_legacy_path(self, overlap_flag,
                                                mp8_mesh, monkeypatch):
        core_flags.set_flags({"comm_overlap": "off"})
        with_hooks = self._sp_layer_jaxpr()
        # the pre-overlap forward, reconstructed by disabling the hook
        monkeypatch.setattr(mp_layers, "maybe_decomposed_column_sp",
                            lambda *a, **k: None)
        legacy = self._sp_layer_jaxpr()
        assert with_hooks == legacy
        # and the decomposed graph is actually different (ppermute ring)
        core_flags.set_flags({"comm_overlap": "tp"})
        decomposed = self._sp_layer_jaxpr()
        assert decomposed != legacy
        assert "ppermute" in decomposed and "ppermute" not in legacy

    def test_off_trainstep_has_no_gather_specs(self, overlap_flag):
        core_flags.set_flags({"comm_overlap": "off"})
        mesh = create_hybrid_mesh(sharding=8)
        set_hybrid_mesh(mesh)
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 8))
        ts = make_sharded_train_step(
            net, AdamW(1e-3),
            lambda m, p, b: jnp.mean(
                (functional_call(m, p, b[0]) - b[1]) ** 2), mesh=mesh)
        assert ts._gather_specs is None

    def test_off_multistep_bitwise_reproducible(self, overlap_flag,
                                                mp8_mesh):
        losses = [self._run_sp_stack("off", steps=2) for _ in range(2)]
        assert losses[0] == losses[1]  # exact float equality

    @staticmethod
    def _run_sp_stack(mode, steps=3, d=16, seq=32, batch=4):
        core_flags.set_flags({"comm_overlap": mode})
        paddle.seed(0)

        class Block(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = ColumnSequenceParallelLinear(
                    d, 4 * d, gather_output=False)
                self.fc2 = RowSequenceParallelLinear(
                    4 * d, d, input_is_parallel=True)

            def forward(self, x):
                x = sequence_parallel_constraint(x)
                return self.fc2(jax.nn.gelu(self.fc1(x)))

        model = nn.Sequential(Block(), Block())

        def loss_fn(m, p, b):
            return jnp.mean((functional_call(m, p, b[0],
                                             training=True) - b[1]) ** 2)

        ts = make_sharded_train_step(model, AdamW(1e-3), loss_fn)
        rng = np.random.default_rng(7)
        out = []
        for i in range(steps):
            x = jnp.asarray(rng.standard_normal((batch, seq, d)),
                            jnp.float32)
            y = jnp.asarray(rng.standard_normal(x.shape), jnp.float32)
            out.append(float(ts.step((x, y))))
        return out


# ---------------------------------------------------------------------------
# Overlapped TP/SP stack: training parity vs the GSPMD step
# ---------------------------------------------------------------------------

class TestSPStackParity:

    def test_tp_loss_parity_multistep(self, overlap_flag, mp8_mesh):
        off = TestFlagOff._run_sp_stack("off")
        tp = TestFlagOff._run_sp_stack("tp")
        np.testing.assert_allclose(tp, off, rtol=1e-5, atol=1e-6)

    def test_tp_grad_parity(self, overlap_flag, mp8_mesh):
        d = 16
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((2, 32, d)), jnp.float32)
        y = jnp.asarray(rng.standard_normal(x.shape), jnp.float32)
        paddle.seed(0)

        class Block(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = ColumnSequenceParallelLinear(
                    d, 4 * d, gather_output=False)
                self.fc2 = RowSequenceParallelLinear(
                    4 * d, d, input_is_parallel=True)

            def forward(self, xx):
                xx = sequence_parallel_constraint(xx)
                return self.fc2(jax.nn.gelu(self.fc1(xx)))

        model = Block()
        params = get_params(model)

        def loss(p):
            return jnp.mean((functional_call(model, p, x,
                                             training=True) - y) ** 2)

        grads = {}
        for mode in ("off", "tp"):
            core_flags.set_flags({"comm_overlap": mode})
            grads[mode] = jitted(jax.grad(loss), params)
        for name in grads["off"]:
            np.testing.assert_allclose(
                np.asarray(grads["tp"][name]),
                np.asarray(grads["off"][name]),
                rtol=2e-4, atol=2e-5, err_msg=name)


# ---------------------------------------------------------------------------
# ZeRO-3 gather-ahead
# ---------------------------------------------------------------------------

class TestZeroGatherAhead:

    def _run(self, mode, steps=4):
        core_flags.set_flags({"comm_overlap": mode})
        mesh = create_hybrid_mesh(sharding=8)
        set_hybrid_mesh(mesh)
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(16, 64), nn.Tanh(),
                            nn.Linear(64, 64), nn.Tanh(),
                            nn.Linear(64, 8))

        def loss_fn(m, p, b):
            return jnp.mean((functional_call(m, p, b[0]) - b[1]) ** 2)

        ts = make_sharded_train_step(net, AdamW(1e-3), loss_fn, mesh=mesh)
        rng = np.random.default_rng(11)
        losses = []
        for _ in range(steps):
            x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
            y = jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
            losses.append(float(ts.step((x, y))))
        set_hybrid_mesh(None)
        return ts, losses

    def test_gather_specs_built_on_fsdp_mesh(self, overlap_flag):
        ts, _ = self._run("tp_zero", steps=1)
        assert ts._gather_specs, "tp_zero on sharding=8 must gather-ahead"
        # every gathered spec has the fsdp axis removed
        for spec in ts._gather_specs.values():
            assert "sharding" not in str(spec)

    def test_multistep_loss_parity(self, overlap_flag):
        _, off = self._run("off")
        _, ahead = self._run("tp_zero")
        np.testing.assert_allclose(ahead, off, rtol=1e-5, atol=1e-6)

    def test_spec_without_axis(self):
        f = overlap.spec_without_axis
        assert f(P("sharding", None), "sharding") == P(None, None)
        assert f(P(("sharding", "mp"), None), "sharding") == P("mp", None)
        assert f(P("mp"), "sharding") == P("mp")
        assert f(P(("sharding",)), "sharding") == P(None)


# ---------------------------------------------------------------------------
# DP gradient buckets
# ---------------------------------------------------------------------------

class TestBucketedReducer:

    def _grads(self, seed=0):
        rng = np.random.default_rng(seed)
        return {
            f"p{i}": jnp.asarray(rng.standard_normal(shape), jnp.float32)
            for i, shape in enumerate([(64,), (8, 16), (256,), (4, 4),
                                       (128, 2), (32,)])
        }

    def test_bucketize_greedy_partition(self):
        grads = self._grads()
        r = overlap.BucketedGradReducer(axis="dp", bucket_bytes=512)
        buckets = r.bucketize(grads)
        assert [n for b in buckets for n in b] == list(grads)
        for bucket in buckets:
            assert bucket  # never empty
        # order preserved, first bucket respects the cap where possible
        assert len(buckets) > 1

    @pytest.mark.parametrize("bucket_bytes", [1, 600, 1 << 30])
    def test_bucket_order_independence(self, bucket_bytes):
        """psum of flat buckets == per-parameter psum, bitwise, for every
        bucket partition (the flat concat cannot change any element's
        reduction)."""
        mesh = create_hybrid_mesh(dp=8)
        grads = self._grads()

        def reduce_with(reducer):
            def fn(*gs):
                named = dict(zip(grads, gs))
                if reducer is None:
                    return tuple(lax.psum(g, "dp")
                                 for g in named.values())
                out = reducer.reduce_in_axis(named)
                return tuple(out[n] for n in named)
            specs = tuple(P() for _ in grads)
            return jitted(overlap.shard_map_compat(
                fn, mesh, specs, specs, {"dp"}), *grads.values())

        per_param = reduce_with(None)
        bucketed = reduce_with(overlap.BucketedGradReducer(
            axis="dp", bucket_bytes=bucket_bytes))
        for got, want, name in zip(bucketed, per_param, grads):
            assert np.array_equal(np.asarray(got), np.asarray(want)), name

    def test_reduce_scatter_op_matches_all_reduce(self):
        mesh = create_hybrid_mesh(dp=8)
        grads = self._grads(seed=5)

        def run(op):
            def fn(*gs):
                named = dict(zip(grads, gs))
                out = overlap.BucketedGradReducer(
                    axis="dp", bucket_bytes=700).reduce_in_axis(named, op=op)
                return tuple(out[n] for n in named)
            specs = tuple(P() for _ in grads)
            return jitted(overlap.shard_map_compat(
                fn, mesh, specs, specs, {"dp"}), *grads.values())

        ar = run("all_reduce")
        rs = run("reduce_scatter")
        for got, want in zip(rs, ar):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("bucket_mb", [1, 1024])
    def test_reduce_stacked_matches_mean(self, bucket_mb):
        rng = np.random.default_rng(2)
        stacked = {
            f"g{i}": jnp.asarray(rng.standard_normal((8,) + shape),
                                 jnp.float32)
            for i, shape in enumerate([(16,), (4, 8), (32,)])
        }
        r = overlap.BucketedGradReducer(axis="dp",
                                        bucket_bytes=bucket_mb << 20)
        out = r.reduce_stacked(stacked, mean=True)
        for name, g in stacked.items():
            np.testing.assert_allclose(np.asarray(out[name]),
                                       np.asarray(jnp.mean(g, 0)),
                                       rtol=1e-6, atol=1e-6)

    def test_fused_allreduce_gradients_bucketed_matches_legacy(
            self, overlap_flag):
        """The hybrid_parallel_util entry under FLAGS_comm_overlap=all
        equals the per-param psum chain it replaces."""
        from paddle_tpu.distributed.fleet.utils.hybrid_parallel_util import (
            fused_allreduce_gradients)

        class Ref:
            def __init__(self, g):
                self.grad = g

        mesh = create_hybrid_mesh(dp=8)
        grads = self._grads(seed=9)

        def run(mode):
            core_flags.set_flags({"comm_overlap": mode})

            def fn(*gs):
                refs = [Ref(g) for g in gs]
                fused_allreduce_gradients(refs)
                return tuple(r.grad for r in refs)
            specs = tuple(P() for _ in grads)
            return jitted(overlap.shard_map_compat(
                fn, mesh, specs, specs, {"dp"}), *grads.values())

        legacy = run("off")
        bucketed = run("all")
        for got, want in zip(bucketed, legacy):
            assert np.array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Chunk autotune plumbing
# ---------------------------------------------------------------------------

class TestChunkAutotune:

    def test_forced_flag_wins(self, overlap_flag):
        core_flags.set_flags({"comm_overlap_chunks": 2})
        assert overlap.pick_chunks("allgather_matmul", 8,
                                   (2, 16, 8), (8, 16), "float32", 2) == 2
        # indivisible s_local falls back to 1
        assert overlap.pick_chunks("allgather_matmul", 8,
                                   (2, 16, 8), (8, 16), "float32", 3) == 1

    def test_cache_winner_consulted(self, overlap_flag, tmp_path,
                                    monkeypatch):
        from paddle_tpu.ops._pallas import autotune
        core_flags.set_flags({"comm_overlap_chunks": 0})
        cache = autotune.AutotuneCache(path=str(tmp_path / "cache.json"))
        monkeypatch.setattr(autotune, "_cache", cache)
        key = overlap._chunks_key("allgather_matmul", 8,
                                  (2, 16, 8), (8, 16), "float32")
        cache.put("comm_overlap", key, {"chunks": 4}, 1.0)
        assert overlap.pick_chunks("allgather_matmul", 8,
                                   (2, 16, 8), (8, 16), "float32", 8) == 4
        # cache miss -> 1
        assert overlap.pick_chunks("matmul_reduce_scatter", 8,
                                   (2, 16, 8), (8, 16), "float32", 8) == 1


# ---------------------------------------------------------------------------
# Static ICI accounting (C001-C003)
# ---------------------------------------------------------------------------

class TestCommCheck:

    def test_c001_volume_blowup(self):
        spec = comm_check.CommSpec(
            name="bad", axis_size=4, hops=12, bytes_per_hop=1 << 20,
            collective_bytes=3 << 20, flops_per_hop=10 ** 12)
        assert any(d.rule == "C001" and d.severity == "error"
                   for d in comm_check.check_comm_spec(spec))

    def test_c002_latency_floor(self):
        spec = comm_check.CommSpec(
            name="tiny", axis_size=8, hops=7, bytes_per_hop=1024,
            collective_bytes=7 * 1024, flops_per_hop=10 ** 12)
        assert "C002" in rules_of(comm_check.check_comm_spec(spec))

    def test_c003_transfer_exceeds_compute(self):
        spec = comm_check.CommSpec(
            name="bw_bound", axis_size=4, hops=3,
            bytes_per_hop=64 << 20, collective_bytes=3 * (64 << 20),
            flops_per_hop=10 ** 6)
        assert "C003" in rules_of(comm_check.check_comm_spec(spec))

    def test_compute_bound_spec_is_clean(self):
        # GPT-1.3B MLP up-proj at mp=2 (4h/2 = 4096 local cols): 137
        # GFLOP of concurrent hop matmuls hide the 16 MiB hop transfer
        spec = comm_check.spec_for_allgather_matmul(
            8, 512, 2048, 4096, 4, 2)
        assert comm_check.check_comm_spec(spec) == []

    def test_real_hop_plans_never_resend(self):
        """The shipped schedules move exactly the ring volume (C001 can
        only fire on a permutation-table bug)."""
        for n in (2, 4, 8):
            for spec in (
                    comm_check.spec_for_allgather_matmul(
                        4, 64, 128, 128, n, 4),
                    comm_check.spec_for_matmul_reduce_scatter(
                        4, 64, 128, 128, n, 4)):
                assert not [d for d in comm_check.check_comm_spec(spec)
                            if d.rule == "C001"], (n, spec.name)

    def test_degenerate_axis_silent(self):
        spec = comm_check.CommSpec(
            name="solo", axis_size=1, hops=0, bytes_per_hop=0,
            collective_bytes=0, flops_per_hop=0)
        assert comm_check.check_comm_spec(spec) == []


# ---------------------------------------------------------------------------
# J014: overlap-defeating collectives
# ---------------------------------------------------------------------------

class TestJ014:

    def _mesh(self):
        return create_hybrid_mesh(dp=8)

    def test_positive_per_param_psum_chain(self):
        mesh = self._mesh()
        gs = [jnp.ones((64,), jnp.float32) * i for i in range(5)]

        def chain(*gs):
            return tuple(lax.psum(g, "dp") for g in gs)

        specs = tuple(P() for _ in gs)
        fn = overlap.shard_map_compat(chain, mesh, specs, specs, {"dp"})
        diags = [d for d in lint_fn(fn, *gs) if d.rule == "J014"]
        assert diags, "5 tiny psums must trip the unbucketed-chain rule"
        assert "per-parameter" in diags[0].message
        assert "BucketedGradReducer" in diags[0].hint

    def test_negative_bucketed_flat_psum(self):
        mesh = self._mesh()
        gs = [jnp.ones((64,), jnp.float32)] * 5

        def bucketed(*gs):
            flat = jnp.concatenate([g.ravel() for g in gs])
            return lax.psum(flat, "dp")

        fn = overlap.shard_map_compat(
            bucketed, mesh, tuple(P() for _ in gs), P(), {"dp"})
        assert "J014" not in rules_of(lint_fn(fn, *gs))

    def test_positive_blocking_collective_outside_jit(self):
        """A step that contains jitted regions AND dispatches an eager
        shard_map-wrapped collective between them."""
        mesh = self._mesh()

        def eager_allreduce(x):
            return overlap.shard_map_compat(
                lambda v: lax.psum(v, "dp"), mesh, (P(),), P(), {"dp"})(x)

        inner = jax.jit(lambda x: x * 2.0)

        def step(x):
            y = inner(x)
            y = eager_allreduce(y)      # blocking one-off program
            return inner(y)

        diags = [d for d in lint_fn(step, jnp.ones((16,)))
                 if d.rule == "J014"]
        assert diags, "eager collective between jitted halves must flag"
        assert any("outside the compiled step" in d.message for d in diags)

    def test_negative_collective_inside_jit(self):
        mesh = self._mesh()

        def step(x):
            def body(v):
                return lax.psum(v * 2.0 + 1.0, "dp")
            return overlap.shard_map_compat(
                body, mesh, (P(),), P(), {"dp"})(x)

        fn = jax.jit(step)
        assert "J014" not in rules_of(lint_fn(fn, jnp.ones((1 << 18,))))

    def test_decomposed_programs_lint_clean_of_j014(self, mp8_mesh):
        """The overlap tier's own pipelines must not trip the rule they
        motivated."""
        x = jnp.ones((2, 16, 8), jnp.float32)
        w = jnp.ones((8, 16), jnp.float32)

        def prog(x, w):
            return jnp.sum(overlap.allgather_matmul(
                x, w, mesh=mp8_mesh, chunks=1))

        assert "J014" not in rules_of(lint_fn(prog, x, w))


# ---------------------------------------------------------------------------
# Telemetry: comm phase + trace_view aggregation
# ---------------------------------------------------------------------------

class TestCommTelemetry:

    def test_comm_in_phase_catalog(self):
        from paddle_tpu.observability.step_monitor import PHASES
        assert "comm" in PHASES

    def test_reduce_stacked_records_comm_phase(self):
        from paddle_tpu.observability import step_monitor
        prev = core_flags.get_flags(["telemetry"])
        core_flags.set_flags({"telemetry": "metrics"})
        try:
            step_monitor.reset_default()
            tm = step_monitor.current()
            stacked = {"g": jnp.ones((8, 32), jnp.float32)}
            with tm.step():
                overlap.BucketedGradReducer(axis="dp").reduce_stacked(
                    stacked, mean=True)
            recs = list(tm._steps)
            assert recs and "comm" in recs[-1]["phases"]
        finally:
            core_flags.set_flags(prev)
            step_monitor.reset_default()

    def test_trace_view_comm_summary(self):
        import sys
        sys.path.insert(0, str(__import__("pathlib").Path(
            __file__).resolve().parents[1]))
        from tools.trace_view import comm_summary, render_text, summarize
        steps = [
            {"kind": "step", "step": 1, "total_ms": 10.0,
             "phases": {"device": 8.0, "comm": 1.5}},
            {"kind": "step", "step": 2, "total_ms": 11.0,
             "phases": {"device": 8.5, "comm": 2.0}},
        ]
        spans = [
            {"kind": "span", "name": "comm/allgather_matmul",
             "dur_us": 500.0,
             "attrs": {"hops": 7, "bytes_per_hop": 1 << 20,
                       "axis_size": 8}},
            {"kind": "span", "name": "comm/allgather_matmul",
             "dur_us": 400.0,
             "attrs": {"hops": 7, "bytes_per_hop": 1 << 20,
                       "axis_size": 8}},
            {"kind": "span", "name": "other", "dur_us": 100.0},
        ]
        comm = comm_summary(steps, spans)
        assert comm["phase_total_ms"] == 3.5
        assert comm["phase_steps"] == 2
        agm = comm["decomposed_ops"]["allgather_matmul"]
        assert agm["calls"] == 2 and agm["hops"] == 14
        assert agm["bytes_moved"] == 14 << 20
        text = render_text(summarize(steps, spans))
        assert "comm overlap" in text and "allgather_matmul" in text


# ---------------------------------------------------------------------------
# comm_check per-trace registry (plan_check's declared-vs-actual feed)
# ---------------------------------------------------------------------------

class TestCommSpecRegistry:

    def test_enforce_records_keyed_by_call_site(self, mp8_mesh):
        """enforce() no longer validates-and-discards: while a recording
        is open, every decomposed call site's spec lands in it keyed by
        call site, with the mesh axis it permutes over."""
        from paddle_tpu.analysis import comm_check
        x = jnp.ones((2, 64, 16), jnp.float32)
        w1 = jnp.ones((16, 32), jnp.float32)
        w2 = jnp.ones((32, 16), jnp.float32)
        h = jnp.ones((2, 64, 32), jnp.float32)
        with comm_check.recording() as rec:
            jax.make_jaxpr(lambda x, w: overlap.allgather_matmul(
                x, w, mesh=mp8_mesh, chunks=1))(x, w1)
            jax.make_jaxpr(lambda h, w: overlap.matmul_reduce_scatter(
                h, w, mesh=mp8_mesh, chunks=1))(h, w2)
        sites = {w for w, _ in rec}
        assert sites == {"overlap.allgather_matmul",
                         "overlap.matmul_reduce_scatter"}
        for _, spec in rec:
            assert spec.axis == "mp" and spec.axis_size == 8

    def test_recording_is_scoped_and_nestable(self):
        from paddle_tpu.analysis import comm_check
        spec = comm_check.spec_for_allgather_matmul(8, 512, 2048, 2048,
                                                    4, 2)
        with comm_check.recording() as outer:
            comm_check.record(spec, where="a")
            with comm_check.recording() as inner:
                comm_check.record(spec, where="b")
            comm_check.record(spec, where="c")
        assert [w for w, _ in inner] == ["b"]
        assert [w for w, _ in outer] == ["a", "b", "c"]
        # closed recordings never see later specs
        comm_check.record(spec, where="late")
        assert [w for w, _ in outer] == ["a", "b", "c"]

"""K-best routing backend parity: the numpy planner DP, the jnp
``layered_dp_kbest``, and the Pallas ``tropical_route_kbest`` kernel
(interpret mode) must agree bit-for-bit — same chains, same rank order,
same tie-breaking — including tie-heavy cost landscapes, infeasible rows,
and degenerate empty batches. Plans built from the device path must drive
``ChainExecutor`` failover splicing exactly like numpy-built plans.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.executor import ChainExecutor
from repro.core.planner import RoutePlanner
from repro.core.registry import AnchorRegistry
from repro.core.routing_jax import (
    backtrack_kbest,
    effective_costs,
    layered_dp_kbest,
    route_batched,
    route_batched_kbest,
)
from repro.kernels import ref
from repro.kernels.tropical_route import tropical_route, tropical_route_kbest
from repro.serving.batch_router import plan_batched

from conftest import build_layered_anchor

INF = 1e38


def _numpy_kbest_chains(planner, t, cfg, tau, k):
    """Planner DP chains in raw rank order (reorder=False) as row lists."""
    w = t.latency_ms + (1.0 - t.trust) * cfg.request_timeout_ms
    mask = t.alive & (t.trust >= tau)
    return planner.solve_kbest(t, w, mask, k=k, reorder=False)


def _device_kbest_chains(t, cfg, taus, k, L, planner, use_kernel):
    hops, costs = route_batched_kbest(
        t, L, cfg, taus, k_max=L, k_best=k, planner=planner,
        use_kernel=use_kernel, interpret=use_kernel)
    out = []
    for r in range(len(taus)):
        chains, ccosts = [], []
        for j in range(k):
            if not float(costs[r, j]) < INF:
                break
            chains.append([int(x) for x in hops[r, j] if x >= 0])
            ccosts.append(float(costs[r, j]))
        out.append((chains, ccosts))
    return out


class TestThreeBackendParity:
    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_chains_and_ranks_match_numpy(self, gcfg, use_kernel):
        """Raw DP rank order identical across backends on random tables
        (integer latencies: exactly representable in f32 and f64, so the
        backends see identical tie structure)."""
        for seed in range(3):
            anchor = build_layered_anchor(gcfg, L=12, replicas=4, seed=seed)
            t = anchor.snapshot(0.0)
            t.latency_ms[:] = np.round(t.latency_ms)
            t.trust[:] = np.round(t.trust * 4) / 4    # induce cost ties
            planner = RoutePlanner(12, k_best=4)
            taus = np.array([0.0, 0.6, 0.8])
            dev = _device_kbest_chains(t, gcfg, taus, 4, 12, planner,
                                       use_kernel)
            for i, tau in enumerate(taus):
                chains, costs = _numpy_kbest_chains(planner, t, gcfg,
                                                    float(tau), 4)
                dchains, dcosts = dev[i]
                assert dchains == chains
                for c, d in zip(costs, dcosts):
                    assert d == pytest.approx(c, rel=1e-5)

    def test_jnp_and_kernel_bitwise_identical(self, gcfg):
        """layered_dp_kbest and the Pallas kernel share f32 arithmetic:
        distK/pedge/prank must be bitwise equal, padded blocks included."""
        anchor = build_layered_anchor(gcfg, L=12, replicas=5, seed=1)
        t = anchor.snapshot(0.0)
        taus = np.linspace(0, 0.9, 5)       # R=5: forces blk_r padding
        costs = effective_costs(jnp.asarray(t.latency_ms, jnp.float32),
                                jnp.asarray(t.trust, jnp.float32),
                                jnp.asarray(t.alive),
                                jnp.asarray(taus, jnp.float32),
                                gcfg.request_timeout_ms)
        starts = jnp.asarray(t.layer_start, jnp.int32)
        ends = jnp.asarray(t.layer_end, jnp.int32)
        d1, e1, r1 = layered_dp_kbest(starts, ends, costs, total_layers=12,
                                      k_best=3)
        d2, e2, r2 = tropical_route_kbest(starts, ends, costs,
                                          total_layers=12, k_best=3,
                                          blk_r=4, interpret=True)
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
        np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))

    def test_kernel_matches_numpy_oracle(self):
        """Synthetic layered DAG with deliberate exact ties (integer f32
        costs): kernel == ref.tropical_route_kbest_ref element-wise."""
        rng = np.random.default_rng(3)
        P, L, K, R = 24, 6, 3, 4
        starts = (rng.integers(0, 3, P) * 2).astype(np.int32)
        ends = np.minimum(starts + 2, L).astype(np.int32)
        costs = rng.integers(1, 8, (R, P)).astype(np.float32)  # many ties
        costs[rng.random((R, P)) < 0.2] = 3.0e38
        rd, re, rr = ref.tropical_route_kbest_ref(starts, ends, costs, L, K)
        kd, ke, kr = tropical_route_kbest(
            jnp.asarray(starts), jnp.asarray(ends), jnp.asarray(costs),
            total_layers=L, k_best=K, blk_r=4, interpret=True)
        np.testing.assert_array_equal(np.asarray(kd), rd)
        np.testing.assert_array_equal(np.asarray(ke), re)
        np.testing.assert_array_equal(np.asarray(kr), rr)

    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_infeasible_rows(self, gcfg, use_kernel):
        """Floors above every trust value: INF costs, no chains, and the
        numpy planner agrees the problem is infeasible."""
        anchor = build_layered_anchor(gcfg, L=12, replicas=3, seed=0,
                                      trust_range=(0.5, 0.9))
        t = anchor.snapshot(0.0)
        planner = RoutePlanner(12, k_best=4)
        taus = np.array([0.99, 0.95])
        hops, costs = route_batched_kbest(
            t, 12, gcfg, taus, k_max=12, k_best=4, planner=planner,
            use_kernel=use_kernel, interpret=use_kernel)
        assert np.all(costs >= INF)
        assert np.all(hops == -1)
        chains, _ = _numpy_kbest_chains(planner, t, gcfg, 0.99, 4)
        assert chains == []

    def test_partial_k_feasible(self, gcfg):
        """Fewer than K distinct chains exist: both backends emit the same
        truncated set, INF-padded on device."""
        cfg = gcfg
        anchor = build_layered_anchor(cfg, L=6, segments=(3,), replicas=2,
                                      seed=0)
        t = anchor.snapshot(0.0)    # 2x2 = 4 distinct chains < k=8
        planner = RoutePlanner(6, k_best=8)
        chains, costs = _numpy_kbest_chains(planner, t, cfg, 0.0, 8)
        assert len(chains) == 4
        dev = _device_kbest_chains(t, cfg, np.array([0.0]), 8, 6, planner,
                                   use_kernel=False)
        assert dev[0][0] == chains


@pytest.mark.parametrize("kernel", ["tropical_route", "tropical_route_kbest"])
def test_kernel_ops_are_exact_on_chip(kernel):
    """Two chip-only faults interpret mode cannot show. The one-hot
    boundary gather runs on the MXU, whose default rounds f32 operands to
    bf16: every matmul must ask for HIGHEST. And Mosaic's argmin does not
    promise the first of tied minima: the kernels must not use it."""
    starts = jnp.zeros((8,), jnp.int32)
    costs = jnp.zeros((4, 8), jnp.float32)
    kw = {"k_best": 2} if kernel.endswith("kbest") else {}
    fn = {"tropical_route": tropical_route,
          "tropical_route_kbest": tropical_route_kbest}[kernel]
    text = str(jax.make_jaxpr(
        lambda s, e, c: fn(s, e, c, total_layers=6, **kw))(
            starts, starts, costs))
    n_dots = text.count("dot_general[")
    assert n_dots >= 1
    assert text.count("precision=(Precision.HIGHEST, Precision.HIGHEST)") \
        == n_dots
    assert "argmin" not in text


class TestDegenerateBatches:
    def test_kernel_empty_batch_regression(self):
        """R == 0 used to divide by zero in the grid computation; it must
        return empty (0, L+1) outputs instead."""
        starts = jnp.zeros((8,), jnp.int32)
        ends = jnp.full((8,), 3, jnp.int32)
        costs = jnp.zeros((0, 8), jnp.float32)
        d, p = tropical_route(starts, ends, costs, total_layers=6)
        assert d.shape == (0, 7) and p.shape == (0, 7)
        dk, ek, rk = tropical_route_kbest(starts, ends, costs,
                                          total_layers=6, k_best=4)
        assert dk.shape == (0, 7, 4) and ek.shape == (0, 7, 4)
        assert rk.shape == (0, 7, 4)

    def test_route_batched_empty(self, gcfg, layered_anchor):
        t = layered_anchor.snapshot(0.0)
        ids, costs = route_batched(t, 12, gcfg, np.zeros((0,)), k_max=12)
        assert ids.shape == (0, 12) and costs.shape == (0,)
        hops, ck = route_batched_kbest(t, 12, gcfg, np.zeros((0,)),
                                       k_max=12, k_best=4)
        assert hops.shape == (0, 4, 12) and ck.shape == (0, 4)

    @pytest.mark.parametrize("backend", ["numpy", "jnp", "pallas"])
    def test_empty_registry_plans_infeasible(self, gcfg, backend):
        """A table with no peers routes nothing on every backend: the
        device DPs must not reduce over a zero-width peer axis."""
        t = AnchorRegistry(gcfg).snapshot(0.0)
        assert len(t) == 0
        plans = plan_batched(t, 12, gcfg, np.array([0.0, 0.9]),
                             planner=RoutePlanner(12, k_best=4), k_best=4,
                             backend=backend,
                             interpret=(backend == "pallas"))
        assert [p.feasible for p in plans] == [False, False]

    def test_backtrack_kbest_shapes(self, gcfg, layered_anchor):
        t = layered_anchor.snapshot(0.0)
        taus = np.array([0.0])
        costs = effective_costs(jnp.asarray(t.latency_ms, jnp.float32),
                                jnp.asarray(t.trust, jnp.float32),
                                jnp.asarray(t.alive),
                                jnp.asarray(taus, jnp.float32),
                                gcfg.request_timeout_ms)
        starts = jnp.asarray(t.layer_start, jnp.int32)
        ends = jnp.asarray(t.layer_end, jnp.int32)
        dk, pe, pr = layered_dp_kbest(starts, ends, costs, total_layers=12,
                                      k_best=2)
        hops = backtrack_kbest(starts, pe, pr, total_layers=12, k_max=12)
        assert hops.shape == (1, 2, 12)


class TestDevicePlansDriveFailover:
    def test_device_plan_splices_with_zero_searches(self, gcfg):
        """A plan built by the batched device path must recover a
        mid-chain failure from its precomputed alternates: no planner
        solve, no fresh search."""
        anchor = build_layered_anchor(gcfg, L=6, segments=(3,), replicas=3,
                                      seed=0, trust_range=(0.95, 1.0))
        t = anchor.snapshot(0.0)
        planner = RoutePlanner(6, k_best=6)
        plans = plan_batched(t, 6, gcfg, np.array([0.0]), planner=planner,
                             k_best=6, backend="jnp")
        plan = plans[0]
        assert plan.feasible and len(plan.chain_ids(0)) == 2
        solves_before = planner.stats["solves"]
        failed = plan.chain_ids(0)[1]

        def hop(pid, k, payload):
            return payload, 10.0, pid != failed

        ex = ChainExecutor(gcfg, hop)
        report, _ = ex.execute(plan.chain_ids(0), t, plan=plan)
        assert report.success and report.repaired
        assert ex.plan_repairs == 1                      # from the plan...
        assert planner.stats["solves"] == solves_before  # ...zero searches
        assert failed not in report.chain

    @pytest.mark.parametrize("backend", ["numpy", "jnp", "pallas"])
    def test_all_backends_build_identical_plans(self, gcfg, backend):
        """plan_batched output == planner.plan output (same chains, same
        alternate order) for matching floors, on every backend."""
        anchor = build_layered_anchor(gcfg, L=12, replicas=4, seed=2)
        t = anchor.snapshot(0.0)
        t.latency_ms[:] = np.round(t.latency_ms)
        t.trust[:] = np.round(t.trust * 8) / 8
        planner = RoutePlanner(12, k_best=4)
        for tau in (0.0, 0.7):
            w = t.latency_ms + (1.0 - t.trust) * gcfg.request_timeout_ms
            mask = t.alive & (t.trust >= tau)
            p_np = planner.plan(t, w, mask, k=4)
            p_dev = plan_batched(t, 12, gcfg, np.array([tau]),
                                 planner=planner, k_best=4,
                                 backend=backend,
                                 interpret=(backend == "pallas"))[0]
            assert p_dev.chain_rows == p_np.chain_rows
            for a, b in zip(p_dev.costs, p_np.costs):
                assert a == pytest.approx(b, rel=1e-5)

    def test_batched_numpy_solver_matches_per_request(self, gcfg):
        """solve_kbest_batched row r == solve_kbest with mask row r,
        bit-for-bit (same float64 arithmetic, same tie-break)."""
        anchor = build_layered_anchor(gcfg, L=12, replicas=5, seed=4)
        t = anchor.snapshot(0.0)
        planner = RoutePlanner(12, k_best=4)
        w = t.latency_ms + (1.0 - t.trust) * gcfg.request_timeout_ms
        taus = np.array([0.0, 0.6, 0.8, 0.99])
        masks = t.alive[None, :] & (t.trust[None, :] >= taus[:, None])
        chains_b, costs_b = planner.solve_kbest_batched(t, w, masks, k=4)
        for r, tau in enumerate(taus):
            chains, costs = planner.solve_kbest(t, w, masks[r], k=4)
            assert chains_b[r] == chains
            assert costs_b[r] == costs

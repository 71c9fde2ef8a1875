"""Perf trajectory suite for the analytic hot paths — feeds BENCH_core.json.

Usage (from the repo root):

    PYTHONPATH=src:. python benchmarks/perf_suite.py             # full run:
        times every hot path and writes BENCH_core.json
    PYTHONPATH=src:. python benchmarks/perf_suite.py --quick     # CI gate:
        correctness checks only (closed-form vs chunked reference, chains
        solver vs _MinCostFlow, batch vs scalar equivalence, warm-start
        reschedule vs cold solve, jit cost kernel vs the numpy closed
        form, DVFS governor vs a brute-force frequency grid, gated-sim
        busy/idle/gated/transition energy conservation, and decode-
        boundary preemption: split additivity of the decode integral plus
        end-to-end conservation + the replica-oracle bound on a
        preempting multi-replica run); no timing assertions, no JSON.
        This is what `scripts/test.sh perf` runs.

    --out PATH            where to write the JSON (default <repo>/BENCH_core.json)
    --sizes A,B,C         workload sizes to sweep (default 1000,10000,100000)
    --headline-m M        the capacitated-scheduler headline size (default 50000)
    --ref-direct-max M    largest m at which the _MinCostFlow oracle is run
                          directly (default 10000; it is O(m²k) so the
                          headline reference time is extrapolated from a
                          power-law fit of the directly measured points,
                          with bit-identical objective checks at every
                          direct point and an exact LP-optimality
                          certificate at the headline size)

What is measured:

  * `AnalyticLLMSimulator.decode_cost` (exact closed form) vs the legacy
    chunked loop at τout = 4096 — against chunk=1 (the exact per-step
    reference it must match to ≤1e-9 rel) and chunk=256 (the old
    midpoint approximation, whose error is also recorded);
  * `pass_costs_batch` vs a scalar `pass_costs` loop;
  * `measure_batch` vs sequential `measure` over characterization grids;
  * `core.scheduler.schedule` (vectorized argmin) throughput;
  * `core.scheduler.schedule_capacitated`: chains vs flow oracle;
  * `core.sweep.IncrementalScheduler.reschedule`: warm-start small-delta
    repair vs a cold chains re-solve at the headline size;
  * `core.sweep.pareto_frontier`: the warm ζ grid vs cold zeta_sweep, and
    the exact-breakpoint frontier;
  * the cluster discrete-event sim with memoized phase costs.

Exit status is nonzero iff any correctness gate fails; timing numbers are
recorded, never asserted (no flaky wall-clock assertions in CI).

BENCH_core.json keeps the latest full snapshot, plus a `history` list with
one compact entry per *commit* (hash, wall_s, headline numbers) so the
perf trajectory across PRs stays on record; re-running on the same commit
replaces that commit's entry in place, keeping the best wall_s, instead
of appending duplicates.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

if __package__ in (None, ""):  # `python benchmarks/perf_suite.py`
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.common import synthetic_fleet, timed  # noqa: E402

from repro.configs import PAPER_ZOO, get_config  # noqa: E402
from repro.core import scheduler  # noqa: E402
from repro.core import characterize as characterize_lib  # noqa: E402
from repro.core.energy_model import (  # noqa: E402
    normalized_costs,
    objective_matrix,
)
from repro.core.sweep import IncrementalScheduler, pareto_frontier  # noqa: E402
from repro.data.workloads import WorkloadSpec, alpaca_like_workload  # noqa: E402
from repro.energy import costs as costs_lib  # noqa: E402
from repro.energy.simulator import AnalyticLLMSimulator  # noqa: E402

REPO_ROOT = Path(__file__).resolve().parents[1]

GATE_CONFIGS = {
    "llama2-7b": lambda: PAPER_ZOO["llama2-7b"],
    "mixtral-8x7b": lambda: PAPER_ZOO["mixtral-8x7b"],
    "mistral-7b": lambda: get_config("mistral-7b"),
    "mamba2-130m": lambda: get_config("mamba2-130m"),
    "recurrentgemma-9b": lambda: get_config("recurrentgemma-9b"),
    "deepseek-v3-671b": lambda: get_config("deepseek-v3-671b"),
}


def workload(m: int, seed: int = 0) -> list[tuple[int, int]]:
    return alpaca_like_workload(WorkloadSpec(n_queries=m, seed=seed))


def random_gamma(k: int, rng) -> tuple[float, ...]:
    g = rng.dirichlet(np.ones(k) * rng.uniform(0.5, 3.0))
    return tuple((g / g.sum()).tolist())


# ---------------------------------------------------------------------------
# Correctness gates (shared by --quick and the full run)
# ---------------------------------------------------------------------------


def gate_decode_closed_form(failures: list[str]) -> dict:
    """Closed form must match the chunk=1 per-step reference ≤ 1e-9 rel
    across every family and both KV modes, including window/MoE-breakpoint
    crossings and tiny phases."""
    worst = 0.0
    ranges = [(1, 1), (1, 3), (8, 100), (32, 512), (3000, 2000), (100, 4096)]
    for name, mk in GATE_CONFIGS.items():
        cfg = mk()
        for kv in (True, False):
            sim = AnalyticLLMSimulator(cfg, batch=4, kv_cache=kv,
                                       noise_sigma=0.0)
            for ctx0, n in ranges:
                t1, e1 = sim.decode_cost(ctx0, n)
                t2, e2 = sim.decode_cost_chunked(ctx0, n, chunk=1)
                rel = max(abs(t1 - t2) / max(abs(t2), 1e-300),
                          abs(e1 - e2) / max(abs(e2), 1e-300))
                worst = max(worst, rel)
                if rel > 1e-9:
                    failures.append(
                        f"decode closed-form mismatch: {name} kv={kv} "
                        f"ctx0={ctx0} n={n} rel={rel:.3e}")
    return {"worst_rel_err": worst, "tolerance": 1e-9}


def gate_pass_costs_batch(failures: list[str]) -> dict:
    """pass_costs_batch must agree with scalar pass_costs elementwise."""
    rng = np.random.default_rng(7)
    worst = 0.0
    for name, mk in GATE_CONFIGS.items():
        cfg = mk()
        nt = rng.integers(1, 4096, 64).astype(float)
        ctx = nt + rng.integers(0, 4096, 64)
        bt = rng.integers(1, 64, 64).astype(float)
        for decode in (False, True):
            pcb = costs_lib.pass_costs_batch(cfg, nt, ctx, bt, decode=decode)
            for i in range(len(nt)):
                pc = costs_lib.pass_costs(cfg, nt[i], ctx[i], bt[i],
                                          decode=decode)
                rel = max(abs(pc.flops - pcb.flops[i]) / max(pc.flops, 1e-300),
                          abs(pc.hbm_bytes - pcb.hbm_bytes[i])
                          / max(pc.hbm_bytes, 1e-300))
                worst = max(worst, rel)
                if rel > 1e-12:
                    failures.append(
                        f"pass_costs_batch mismatch: {name} decode={decode} "
                        f"i={i} rel={rel:.3e}")
    return {"worst_rel_err": worst, "tolerance": 1e-12}


def gate_measure_batch(failures: list[str]) -> dict:
    """measure_batch must be noise-stream-identical to sequential measure."""
    cfg = PAPER_ZOO["llama2-7b"]
    pts = [(8, 8), (64, 32), (8, 8), (128, 16), (512, 256), (64, 32)]
    s1 = AnalyticLLMSimulator(cfg, seed=9)
    s2 = AnalyticLLMSimulator(cfg, seed=9)
    seq = [s1.measure(a, b) for a, b in pts]
    e, r = s2.measure_batch([p[0] for p in pts], [p[1] for p in pts])
    ok = all(sv[0] == e[i] and sv[1] == r[i] for i, sv in enumerate(seq))
    if not ok:
        failures.append("measure_batch diverges from sequential measure")
    return {"stream_identical": ok}


def gate_capacitated_solver(failures: list[str], *, n_instances: int = 8,
                            m_max: int = 400) -> dict:
    """chains solver vs _MinCostFlow: objectives must be bit-identical."""
    n_exact = 0
    for t in range(n_instances):
        rng = np.random.default_rng(5000 + t)
        m = int(rng.integers(10, m_max))
        k = int(rng.integers(2, 7))
        qs = [(int(a), int(b)) for a, b in
              zip(rng.integers(1, 4096, m), rng.integers(1, 4096, m))]
        profs = synthetic_fleet(k, seed=t)
        gamma = random_gamma(k, rng)
        zeta = float(rng.uniform(0, 1))
        a = scheduler.schedule_capacitated(profs, qs, zeta, gamma,
                                           method="chains")
        b = scheduler.schedule_capacitated(profs, qs, zeta, gamma,
                                           method="flow")
        if a.objective == b.objective:
            n_exact += 1
        elif abs(a.objective - b.objective) > 1e-12 * max(1.0,
                                                          abs(b.objective)):
            # 1e-12 rel, not == : permuted exact optima over duplicate
            # queries can differ in the last ulp of the pairwise sum
            failures.append(
                f"capacitated solver mismatch: instance {t} m={m} k={k} "
                f"chains={a.objective!r} flow={b.objective!r}")
        costs = normalized_costs(profs, qs)
        C = objective_matrix(costs, zeta)
        caps = scheduler._capacities_from_gamma(gamma, m)
        if not scheduler.capacitated_optimality_certificate(C, a.assignee, caps):
            failures.append(f"optimality certificate failed: instance {t}")
    return {"instances": n_instances, "bit_identical": n_exact}


def gate_warm_start(failures: list[str], *, n_instances: int = 12) -> dict:
    """IncrementalScheduler.reschedule after a randomized delta (adds,
    removals, capacity shifts, ζ moves) must match a cold
    schedule_capacitated solve on the identical workload: objective within
    the chains-vs-flow 1e-12-relative equivalence class and the exact
    LP-optimality certificate (asserted via check=True)."""
    n_bit = 0
    for t in range(n_instances):
        rng = np.random.default_rng(8100 + t)
        m = int(rng.integers(10, 300))
        k = int(rng.integers(2, 7))
        qs = [(int(a), int(b)) for a, b in
              zip(rng.integers(1, 4096, m), rng.integers(1, 4096, m))]
        profs = synthetic_fleet(k, seed=t)
        gamma = random_gamma(k, rng)
        zeta = float(rng.uniform(0, 1))
        inc = IncrementalScheduler(profs, qs, zeta, gamma, check=True)
        n_add = int(rng.integers(0, 8))
        n_rem = int(rng.integers(0, min(8, m - 1)))
        added = [(int(a), int(b)) for a, b in
                 zip(rng.integers(1, 4096, n_add),
                     rng.integers(1, 4096, n_add))]
        removed = list(rng.choice(inc.active_ids, size=n_rem, replace=False))
        z2 = float(np.clip(zeta + rng.uniform(-0.2, 0.2), 0, 1))
        try:
            asg = inc.reschedule(added=added, removed=removed, zeta=z2)
        except RuntimeError as e:
            failures.append(f"warm-start reschedule failed: instance {t}: {e}")
            continue
        cold = scheduler.schedule_capacitated(profs, inc.active_queries(),
                                              z2, gamma)
        if asg.objective == cold.objective:
            n_bit += 1
        elif abs(asg.objective - cold.objective) > 1e-12 * max(
                1.0, abs(cold.objective)):
            failures.append(
                f"warm-start objective mismatch: instance {t} "
                f"warm={asg.objective!r} cold={cold.objective!r}")
    return {"instances": n_instances, "bit_identical": n_bit}


def gate_dvfs_closed_form(failures: list[str]) -> dict:
    """The per-phase DVFS governor's closed-form frequency choice must
    match a brute-force sweep of the same operating-point grid evaluated
    with the chunk=1 per-step reference loop — same argmin scale, same
    energy to 1e-9 — and the scaled closed forms themselves must match the
    reference at every grid point."""
    worst = 0.0
    n_checked = 0
    for name in ("llama2-7b", "mixtral-8x7b"):
        cfg = GATE_CONFIGS[name]()
        for kv in (True, False):
            sim = AnalyticLLMSimulator(cfg, batch=1, kv_cache=kv,
                                       noise_sigma=0.0)
            host = sim.host_power_w
            for ctx0, n in ((32, 200), (1024, 64)):
                grid = {}
                for s in sim.node.accel.dvfs_scales:
                    t_c, e_c = sim.decode_cost(ctx0, n, 4, freq_scale=s)
                    t_r, e_r = sim.decode_cost_chunked(ctx0, n, 4, chunk=1,
                                                       freq_scale=s)
                    rel = max(abs(t_c - t_r) / max(abs(t_r), 1e-300),
                              abs(e_c - e_r) / max(abs(e_r), 1e-300))
                    worst = max(worst, rel)
                    if rel > 1e-9:
                        failures.append(
                            f"scaled decode closed-form mismatch: {name} "
                            f"kv={kv} s={s} rel={rel:.3e}")
                    grid[s] = (t_r, e_r)
                s_gov, t_gov, e_gov = sim.best_decode_frequency(
                    ctx0, n, 4, extra_w=host)
                # brute force applies the governor's own tie rule (1e-12
                # relative band, higher clock wins ties) to the reference
                # values, so a near-tie between operating points cannot
                # flip the gate on an fp hair
                s_bf, bf_tot = None, None
                for s, (t_r, e_r) in grid.items():
                    tot = e_r + host * t_r
                    if bf_tot is None or tot < bf_tot - 1e-12 * max(
                            1.0, abs(bf_tot)):
                        s_bf, bf_tot = s, tot
                    elif abs(tot - bf_tot) <= 1e-12 * max(
                            1.0, abs(bf_tot)) and s > s_bf:
                        s_bf, bf_tot = s, tot
                gov_tot = e_gov + host * t_gov
                n_checked += 1
                choice_ok = (s_gov == s_bf
                             or abs(gov_tot - bf_tot) <= 1e-9 * max(
                                 1.0, abs(bf_tot)))
                if not choice_ok or gov_tot > bf_tot * (1 + 1e-9) + 1e-9:
                    failures.append(
                        f"DVFS governor vs brute force: {name} kv={kv} "
                        f"ctx0={ctx0} n={n}: chose {s_gov} ({gov_tot!r} J) "
                        f"vs grid {s_bf} ({bf_tot!r} J)")
    return {"worst_rel_err": worst, "tolerance": 1e-9,
            "choices_checked": n_checked}


def gate_preemption_split(failures: list[str]) -> dict:
    """Decode-boundary preemption must conserve energy exactly.

    (a) The closed-form decode integral is additive at any split point:
        decode_cost(c, a) + decode_cost(c+a, b) == decode_cost(c, a+b)
        to 1e-9 rel, across model families, both KV modes and a scaled
        operating point — this is the identity that makes a preempted
        segment's two halves sum to the unpreempted cost.
    (b) A preempting multi-replica cluster run conserves end to end: all
        requests served, preemptions actually fire and every preemption
        has a matching resume, the four buckets still partition each
        node's horizon, per-request attributed energies sum to the busy
        bucket, and the replica-aware oracle replay is never worse than
        the online policy on the Eq. 2 objective."""
    worst = 0.0
    splits = [(64, 300, 1), (64, 300, 150), (64, 300, 299),
              (1000, 64, 20), (8, 2048, 777)]
    for name in ("llama2-7b", "mixtral-8x7b", "mamba2-130m"):
        cfg = GATE_CONFIGS[name]()
        for kv in (True, False):
            sim = AnalyticLLMSimulator(cfg, batch=4, kv_cache=kv,
                                       noise_sigma=0.0)
            for s in (1.0, sim.node.accel.dvfs_scales[0]):
                for ctx0, n, cut in splits:
                    t, e = sim.decode_cost(ctx0, n, freq_scale=s)
                    t1, e1 = sim.decode_cost(ctx0, cut, freq_scale=s)
                    t2, e2 = sim.decode_cost(ctx0 + cut, n - cut,
                                             freq_scale=s)
                    rel = max(abs(t1 + t2 - t) / max(abs(t), 1e-300),
                              abs(e1 + e2 - e) / max(abs(e), 1e-300))
                    worst = max(worst, rel)
                    if rel > 1e-9:
                        failures.append(
                            f"preemption split not additive: {name} kv={kv} "
                            f"s={s} ctx0={ctx0} n={n} cut={cut} "
                            f"rel={rel:.3e}")

    from repro.cluster import (ClusterNode, ReplicaEnergyPolicy,
                               ReplicaOraclePolicy, SLOPreemptionPolicy,
                               poisson_trace, simulate_cluster)
    from repro.configs import TABLE1
    from repro.core.energy_model import fit_profile
    from repro.energy import SWING_NODE

    fleet = ("llama2-7b", "llama2-13b")
    profiles = {}
    for name in fleet:
        sim = AnalyticLLMSimulator(PAPER_ZOO[name], SWING_NODE, batch=1,
                                   kv_cache=True, noise_sigma=0.0)
        pts = [(8, 8), (64, 64), (256, 128), (512, 512), (128, 32)]
        pbs = [sim.simulate(a, b) for a, b in pts]
        profiles[name] = fit_profile(
            name, TABLE1[name]["a_k"],
            [p[0] for p in pts], [p[1] for p in pts],
            [pb.energy_j for pb in pbs], [pb.runtime_s for pb in pbs])

    def nodes():   # two replicas per model, tiny batches force contention
        return [ClusterNode(2 * i + j, PAPER_ZOO[name], profiles[name],
                            SWING_NODE, max_batch=2)
                for i, name in enumerate(fleet) for j in (0, 1)]

    trace = poisson_trace(60, 6.0, seed=3)
    preempter = SLOPreemptionPolicy(slowdown_slo=1.2, min_remaining=2)
    rep = simulate_cluster(trace, nodes(), ReplicaEnergyPolicy(), zeta=0.5,
                           preempter=preempter)
    oracle = simulate_cluster(
        trace, nodes(), ReplicaOraclePolicy(), zeta=0.5,
        preempter=SLOPreemptionPolicy(slowdown_slo=1.2, min_remaining=2))
    if len(rep.records) != len(trace):
        failures.append("preemption gate lost requests")
    if rep.total_preemptions == 0:
        failures.append("preemption gate saw no preemptions")
    if rep.total_preemptions != rep.total_resumes:
        failures.append(
            f"preemptions ({rep.total_preemptions}) != resumes "
            f"({rep.total_resumes})")
    worst_e = worst_t = 0.0
    for s in rep.node_stats:
        e_sum = (s.busy_energy_j + s.idle_energy_j + s.gated_energy_j
                 + s.transition_energy_j)
        worst_e = max(worst_e, abs(e_sum - s.total_energy_j)
                      / max(1.0, s.total_energy_j))
        worst_t = max(worst_t, abs(s.accounted_s - s.horizon_s)
                      / max(1.0, s.horizon_s))
    attributed = sum(r.energy_j for r in rep.records)
    busy = sum(s.busy_energy_j for s in rep.node_stats)
    worst_e = max(worst_e, abs(attributed - busy) / max(1.0, busy))
    if worst_e > 1e-9 or worst_t > 1e-9:
        failures.append(
            f"preempting run violates conservation: energy rel "
            f"{worst_e:.3e}, time rel {worst_t:.3e}")
    if oracle.objective > rep.objective + 1e-9:
        failures.append(
            f"replica oracle beaten on objective: {oracle.objective!r} > "
            f"{rep.objective!r}")
    return {"worst_split_rel": worst, "worst_energy_rel": worst_e,
            "worst_time_rel": worst_t, "tolerance": 1e-9,
            "preemptions": rep.total_preemptions,
            "resumes": rep.total_resumes}


def gate_migration_settlement(failures: list[str]) -> dict:
    """Cross-node migration rescue must settle exactly, end to end.

    (a) A scripted crash storm over a 2-replica fleet, run under a live
        InvariantAuditor (every donor truncated charge, waste move and
        KV shipment checked at 1e-9 as it happens): migrations must
        actually fire, the six energy buckets must partition each node's
        horizon exactly, and per-request attributed energy must still
        sum to the fleet busy bucket — the cross-node split contract.
    (b) The shipping bucket must follow the interconnect closed form in
        aggregate: Σ shipped KV bytes × j_per_byte_ici == the fleet
        shipping energy, and bytes / ici_bw == the shipping seconds
        (uniform hardware, so the totals close without per-event state).
    (c) A crash with no same-model survivor books the refugees'
        accrued joules as wasted and their requests as abandoned —
        conservation closes through the waste bucket, never a leak."""
    from repro.cluster import (ClusterNode, FailoverPolicy, FaultEvent,
                               FaultTrace, LeastLoadedPolicy,
                               ZetaOnlinePolicy, poisson_trace,
                               simulate_cluster)
    from repro.cluster.faults import CRASH, RECOVER
    from repro.configs import TABLE1
    from repro.core.energy_model import fit_profile
    from repro.energy import SWING_NODE
    from repro.energy.costs import kv_bytes_per_token
    from repro.obs import InvariantAuditor, InvariantViolation, Telemetry

    fleet = ("llama2-7b", "llama2-7b", "llama2-13b")
    profiles = {}
    for name in set(fleet):
        sim = AnalyticLLMSimulator(PAPER_ZOO[name], SWING_NODE, batch=1,
                                   kv_cache=True, noise_sigma=0.0)
        pts = [(8, 8), (64, 64), (256, 128), (512, 512), (128, 32)]
        pbs = [sim.simulate(a, b) for a, b in pts]
        profiles[name] = fit_profile(
            name, TABLE1[name]["a_k"],
            [p[0] for p in pts], [p[1] for p in pts],
            [pb.energy_j for pb in pbs], [pb.runtime_s for pb in pbs])

    def nodes(names=fleet):
        return [ClusterNode(i, PAPER_ZOO[name], profiles[name], SWING_NODE,
                            max_batch=2)
                for i, name in enumerate(names)]

    # (a)+(b): alternate crashing each 7b replica so refugees ship to the
    # surviving one; high rate keeps decodes in flight at crash time
    trace = poisson_trace(60, 6.0, seed=3)
    storm = FaultTrace("storm", tuple(
        FaultEvent(t, nid, kind)
        for t, nid, kind in ((1.5, 0, CRASH), (4.0, 0, RECOVER),
                             (5.0, 1, CRASH), (8.0, 1, RECOVER),
                             (9.0, 0, CRASH), (12.0, 0, RECOVER))))
    tel = Telemetry(auditor=InvariantAuditor())
    try:
        rep = simulate_cluster(trace, nodes(), FailoverPolicy(
            ZetaOnlinePolicy()), zeta=0.5, faults=storm, telemetry=tel)
    except InvariantViolation as e:
        failures.append(f"migration gate tripped the live auditor: {e}")
        return {"auditor": "violated"}
    if rep.total_migrations == 0:
        failures.append("migration gate saw no migrations")
    if rep.total_crashes == 0:
        failures.append("migration gate saw no crashes")
    worst_e = worst_t = 0.0
    for s in rep.node_stats:
        e_sum = (s.busy_energy_j + s.idle_energy_j + s.gated_energy_j
                 + s.transition_energy_j + s.shipping_energy_j
                 + s.wasted_energy_j)
        worst_e = max(worst_e, abs(e_sum - s.total_energy_j)
                      / max(1.0, s.total_energy_j))
        worst_t = max(worst_t, abs(s.accounted_s - s.horizon_s)
                      / max(1.0, s.horizon_s))
    attributed = sum(r.energy_j for r in rep.records)
    busy = sum(s.busy_energy_j for s in rep.node_stats)
    worst_e = max(worst_e, abs(attributed - busy) / max(1.0, busy))
    if worst_e > 1e-9 or worst_t > 1e-9:
        failures.append(
            f"faulted run violates six-bucket conservation: energy rel "
            f"{worst_e:.3e}, time rel {worst_t:.3e}")
    # (b): aggregate interconnect closed form (uniform SWING hardware)
    accel = SWING_NODE.accel
    shipped = sum(r.shipped_bytes for r in rep.records)
    ship_j = sum(s.shipping_energy_j for s in rep.node_stats)
    ship_s = sum(s.shipping_s for s in rep.node_stats)
    rel_j = (abs(ship_j - shipped * accel.j_per_byte_ici)
             / max(1.0, ship_j))
    rel_s = (abs(ship_s - shipped / accel.ici_bw) / max(1.0, ship_s))
    if shipped <= 0.0:
        failures.append("migration gate shipped no KV bytes")
    if rel_j > 1e-9 or rel_s > 1e-9:
        failures.append(
            f"shipping bucket off the interconnect closed form: energy "
            f"rel {rel_j:.3e}, time rel {rel_s:.3e}")
    # (c): lone node crashes mid-run and never recovers — no survivor,
    # so in-flight work is wasted and the rest abandoned, books closed
    lone_trace = poisson_trace(10, 4.0, seed=5)
    lone = simulate_cluster(
        lone_trace, nodes(("llama2-7b",)),
        FailoverPolicy(LeastLoadedPolicy(), max_retries=2), zeta=0.5,
        faults=FaultTrace("lone", (FaultEvent(0.8, 0, CRASH),)))
    if not lone.abandoned:
        failures.append("no-survivor crash abandoned nothing")
    if len(lone.records) + len(lone.abandoned) != len(lone_trace):
        failures.append("no-survivor crash lost requests")
    wasted = sum(s.wasted_energy_j for s in lone.node_stats)
    if wasted <= 0.0:
        failures.append("no-survivor crash booked no wasted energy")
    lone_rel = max(
        abs((s.busy_energy_j + s.idle_energy_j + s.gated_energy_j
             + s.transition_energy_j + s.shipping_energy_j
             + s.wasted_energy_j) - s.total_energy_j)
        / max(1.0, s.total_energy_j)
        for s in lone.node_stats)
    if lone_rel > 1e-9:
        failures.append(
            f"no-survivor waste leaks energy: rel {lone_rel:.3e}")
    return {"worst_energy_rel": worst_e, "worst_time_rel": worst_t,
            "shipping_energy_rel": rel_j, "shipping_time_rel": rel_s,
            "tolerance": 1e-9, "crashes": rep.total_crashes,
            "migrations": rep.total_migrations,
            "shipped_bytes": shipped,
            "auditor_checks": tel.auditor.n_checks,
            "no_survivor_abandoned": len(lone.abandoned),
            "no_survivor_wasted_j": wasted}


def gate_checkpoint_settlement(failures: list[str]) -> dict:
    """Prefill checkpointing must settle exactly, end to end.

    (a) Telescoping: with no faults a checkpointed run must match the
        unchunked run per request to 1e-9 in finish time and energy —
        chunk costs are exact prefix differences of `prefill_cost` at
        one pinned operating point, so Σ chunks == one prefill.
    (b) Aggregate storage closed form: every interior boundary persists
        exactly `interval_tokens` of new KV, so over the whole fleet
        Σ checkpoint energy == n_checkpoints × interval × kv_bytes ×
        j_per_byte_ckpt and Σ checkpoint seconds == bytes / ckpt_bw
        (uniform config, so the totals close without per-event state).
    (c) A scripted mid-prefill crash under a live InvariantAuditor
        restores from the last durable boundary on the survivor: one
        restore, only the durable prefix ships, the in-flight chunk is
        the only waste, and the seven buckets partition each node's
        horizon exactly."""
    from repro.cluster import (CheckpointConfig, ClusterNode,
                               FailoverPolicy, FaultEvent, FaultTrace,
                               LeastLoadedPolicy, simulate_cluster,
                               timestamped_trace)
    from repro.cluster.faults import CRASH
    from repro.configs import TABLE1
    from repro.core.energy_model import fit_profile
    from repro.energy import SWING_NODE
    from repro.energy.costs import kv_bytes_per_token
    from repro.obs import InvariantAuditor, InvariantViolation, Telemetry

    name = "llama2-7b"
    sim = AnalyticLLMSimulator(PAPER_ZOO[name], SWING_NODE, batch=1,
                               kv_cache=True, noise_sigma=0.0)
    pts = [(8, 8), (64, 64), (256, 128), (512, 512), (2048, 64)]
    pbs = [sim.simulate(a, b) for a, b in pts]
    profile = fit_profile(name, TABLE1[name]["a_k"],
                          [p[0] for p in pts], [p[1] for p in pts],
                          [pb.energy_j for pb in pbs],
                          [pb.runtime_s for pb in pbs])
    interval = 256
    kvb = kv_bytes_per_token(PAPER_ZOO[name])
    ck = CheckpointConfig(interval_tokens=interval)

    def nodes(checkpoint):
        return [ClusterNode(i, PAPER_ZOO[name], profile, SWING_NODE,
                            max_batch=2, checkpoint=checkpoint)
                for i in range(2)]

    # (a)+(b): prefill-heavy trace with interior boundaries at several
    # depths; identical runs modulo the checkpoint layer
    shapes = [(0.0, (2048, 16)), (0.5, (1024, 32)), (1.0, (300, 64)),
              (4.0, (512, 16)), (6.0, (768, 8)), (9.0, (1536, 24))]
    trace = timestamped_trace(shapes, name="ckpt-settle")
    plain = simulate_cluster(trace, nodes(None),
                             FailoverPolicy(LeastLoadedPolicy()), zeta=0.5)
    ckpt = simulate_cluster(trace, nodes(ck),
                            FailoverPolicy(LeastLoadedPolicy()), zeta=0.5)
    worst_tel = 0.0
    for a, b in zip(plain.records, ckpt.records):
        worst_tel = max(worst_tel,
                        abs(a.finish_s - b.finish_s) / max(1.0, a.finish_s),
                        abs(a.energy_j - b.energy_j) / max(1.0, a.energy_j))
    if worst_tel > 1e-9:
        failures.append(
            f"checkpoint telescoping drifted off the unchunked run: rel "
            f"{worst_tel:.3e}")
    n_ckpts = ckpt.total_checkpoints
    if n_ckpts == 0:
        failures.append("checkpoint gate persisted no boundaries")
    bytes_ckpt = n_ckpts * interval * kvb
    rel_j = (abs(ckpt.total_checkpoint_energy_j
                 - bytes_ckpt * ck.j_per_byte_ckpt)
             / max(1.0, ckpt.total_checkpoint_energy_j))
    ckpt_s = sum(s.checkpoint_s for s in ckpt.node_stats)
    rel_s = abs(ckpt_s - bytes_ckpt / ck.ckpt_bw) / max(1.0, ckpt_s)
    if rel_j > 1e-9 or rel_s > 1e-9:
        failures.append(
            f"checkpoint bucket off the storage closed form: energy rel "
            f"{rel_j:.3e}, time rel {rel_s:.3e}")
    # (c): crash strictly inside the 5th chunk — 1024 tokens durable
    cn = nodes(ck)
    t1, e1 = cn[0].sim.prefill_cost(1024, batch=1, freq_scale=1.0)
    t2, e2 = cn[0].sim.prefill_cost(1280, batch=1, freq_scale=1.0)
    tel = Telemetry(auditor=InvariantAuditor())
    try:
        rescue = simulate_cluster(
            timestamped_trace([(0.0, (2048, 8))]), cn,
            FailoverPolicy(LeastLoadedPolicy()), zeta=0.5,
            faults=FaultTrace("mid", (FaultEvent((t1 + t2) / 2.0, 0,
                                                 CRASH),)),
            telemetry=tel)
    except InvariantViolation as e:
        failures.append(f"checkpoint gate tripped the live auditor: {e}")
        return {"auditor": "violated"}
    if rescue.total_restores != 1 or rescue.abandoned:
        failures.append(
            f"mid-prefill crash did not restore once cleanly: "
            f"{rescue.total_restores} restores, "
            f"{len(rescue.abandoned)} abandoned")
    shipped = sum(r.shipped_bytes for r in rescue.records)
    rel_ship = abs(shipped - 1024 * kvb) / max(1.0, shipped)
    chunk_j = (e2 - e1) + cn[0].sim.host_power_w * (t2 - t1)
    rel_waste = (abs(rescue.total_wasted_energy_j - chunk_j)
                 / max(1.0, chunk_j))
    if rel_ship > 1e-9 or rel_waste > 1e-9:
        failures.append(
            f"restore settlement off closed form: shipped rel "
            f"{rel_ship:.3e}, wasted rel {rel_waste:.3e}")
    worst_e = worst_t = 0.0
    for rep in (ckpt, rescue):
        for s in rep.node_stats:
            e_sum = (s.busy_energy_j + s.idle_energy_j + s.gated_energy_j
                     + s.transition_energy_j + s.shipping_energy_j
                     + s.checkpoint_energy_j + s.wasted_energy_j)
            worst_e = max(worst_e, abs(e_sum - s.total_energy_j)
                          / max(1.0, s.total_energy_j))
            worst_t = max(worst_t, abs(s.accounted_s - s.horizon_s)
                          / max(1.0, s.horizon_s))
    if worst_e > 1e-9 or worst_t > 1e-9:
        failures.append(
            f"checkpointed run violates seven-bucket conservation: energy "
            f"rel {worst_e:.3e}, time rel {worst_t:.3e}")
    return {"telescoping_rel": worst_tel, "checkpoint_energy_rel": rel_j,
            "checkpoint_time_rel": rel_s, "worst_energy_rel": worst_e,
            "worst_time_rel": worst_t, "tolerance": 1e-9,
            "checkpoints": n_ckpts, "checkpoint_bytes": bytes_ckpt,
            "restores": rescue.total_restores,
            "auditor_checks": tel.auditor.n_checks}


def gate_prefix_cache_settlement(failures: list[str]) -> dict:
    """The KV prefix cache must settle exactly, end to end.

    (a) Warm-suffix telescoping: a scripted two-turn session's warm
        record is charged exactly prefill_cost(τin) − prefill_cost(cached)
        plus its decode — the same prefix-difference contract restores
        use — to 1e-9.
    (b) Cache-read closed form: fleet Σ cache-read joules ==
        Σ hits cached × kv_bytes × j_per_byte_read (and seconds ==
        bytes / read_bw), the eighth bucket.
    (c) Default-off identity: a cache-equipped fleet serving sessionless
        traffic is byte-identical to a cache-free fleet.
    (d) A session storm with tight capacity (LRU churn) and a crash
        (cache invalidation) under a live InvariantAuditor keeps the
        eight-bucket partition exact."""
    from repro.cluster import (ArrivalTrace, ClusterNode, FaultInjector,
                               LeastLoadedPolicy, PrefixCacheConfig,
                               SessionAffinityPolicy, TracedRequest,
                               poisson_trace, session_trace,
                               simulate_cluster)
    from repro.configs import TABLE1
    from repro.core.energy_model import fit_profile
    from repro.energy import SWING_NODE
    from repro.energy.costs import kv_bytes_per_token
    from repro.obs import InvariantAuditor, InvariantViolation, Telemetry

    name = "llama2-7b"
    sim = AnalyticLLMSimulator(PAPER_ZOO[name], SWING_NODE, batch=1,
                               kv_cache=True, noise_sigma=0.0)
    pts = [(8, 8), (64, 64), (256, 128), (512, 512), (2048, 64)]
    pbs = [sim.simulate(a, b) for a, b in pts]
    profile = fit_profile(name, TABLE1[name]["a_k"],
                          [p[0] for p in pts], [p[1] for p in pts],
                          [pb.energy_j for pb in pbs],
                          [pb.runtime_s for pb in pbs])
    kvb = kv_bytes_per_token(PAPER_ZOO[name])

    def nodes(cache, n=1):
        return [ClusterNode(i, PAPER_ZOO[name], profile, SWING_NODE,
                            max_batch=2, prefix_cache=cache)
                for i in range(n)]

    # (a)+(b): one session, two far-apart turns on one node
    pc = PrefixCacheConfig()
    trace = ArrivalTrace(name="warm", requests=(
        TracedRequest(0, 0.0, 512, 32, session_id=0, turn=0,
                      prefix_tokens=0),
        TracedRequest(1, 60.0, 800, 32, session_id=0, turn=1,
                      prefix_tokens=544),
    ))
    rep = simulate_cluster(trace, nodes(pc), LeastLoadedPolicy(), zeta=0.5)
    warm = rep.records[-1]
    t2, e2 = sim.prefill_cost(800, batch=1, freq_scale=1.0)
    t1, e1 = sim.prefill_cost(544, batch=1, freq_scale=1.0)
    td, ed = sim.decode_cost(800, 32, batch=1, freq_scale=1.0)
    want = (e2 - e1) + ed + sim.host_power_w * ((t2 - t1) + td)
    rel_warm = abs(warm.energy_j - want) / max(1.0, want)
    if warm.cached_tokens != 544 or rel_warm > 1e-9:
        failures.append(
            f"warm suffix charge off the telescoped closed form: cached "
            f"{warm.cached_tokens}, energy rel {rel_warm:.3e}")
    read_bytes = 544 * kvb
    rel_read_j = (abs(rep.total_cache_read_energy_j
                      - read_bytes * pc.j_per_byte_read)
                  / max(1e-12, rep.total_cache_read_energy_j))
    read_s = sum(s.cache_read_s for s in rep.node_stats)
    rel_read_s = abs(read_s - read_bytes / pc.read_bw) / max(1e-12, read_s)
    if rel_read_j > 1e-9 or rel_read_s > 1e-9:
        failures.append(
            f"cache-read bucket off closed form: energy rel "
            f"{rel_read_j:.3e}, time rel {rel_read_s:.3e}")

    # (c): sessionless traffic must not see the cache at all
    plain_trace = poisson_trace(30, 4.0, seed=3)
    with_cache = simulate_cluster(plain_trace, nodes(pc, n=2),
                                  LeastLoadedPolicy(), zeta=0.5)
    without = simulate_cluster(plain_trace, nodes(None, n=2),
                               LeastLoadedPolicy(), zeta=0.5)
    identical = (with_cache.to_json(include_records=True)
                 == without.to_json(include_records=True))
    if not identical:
        failures.append(
            "cache-equipped fleet diverged from cache-free on "
            "sessionless traffic")

    # (d): storm with LRU churn + crash invalidation, live-audited
    tight = PrefixCacheConfig(capacity_bytes=600 * kvb)
    storm_trace = session_trace(8, turns=5, think_s=4.0, rate_qps=1.0,
                                seed=5)
    faults = FaultInjector(mttf_s=25.0, mttr_s=5.0, seed=7).generate(
        [0, 1, 2], storm_trace.duration_s)
    tel = Telemetry(auditor=InvariantAuditor())
    try:
        storm = simulate_cluster(
            storm_trace, nodes(tight, n=3), SessionAffinityPolicy(),
            zeta=0.5, faults=faults, telemetry=tel)
    except InvariantViolation as e:
        failures.append(f"prefix-cache gate tripped the live auditor: {e}")
        return {"auditor": "violated"}
    worst_e = worst_t = 0.0
    for s in storm.node_stats:
        e_sum = (s.busy_energy_j + s.idle_energy_j + s.gated_energy_j
                 + s.transition_energy_j + s.shipping_energy_j
                 + s.checkpoint_energy_j + s.wasted_energy_j
                 + s.cache_read_energy_j)
        worst_e = max(worst_e, abs(e_sum - s.total_energy_j)
                      / max(1.0, s.total_energy_j))
        worst_t = max(worst_t, abs(s.accounted_s - s.horizon_s)
                      / max(1.0, s.horizon_s))
    if worst_e > 1e-9 or worst_t > 1e-9:
        failures.append(
            f"cached run violates eight-bucket conservation: energy rel "
            f"{worst_e:.3e}, time rel {worst_t:.3e}")
    if storm.total_cache_hits + storm.total_cache_misses == 0:
        failures.append("prefix-cache storm never consulted the cache")
    return {"warm_charge_rel": rel_warm, "cache_read_energy_rel": rel_read_j,
            "cache_read_time_rel": rel_read_s,
            "sessionless_identical": identical,
            "worst_energy_rel": worst_e, "worst_time_rel": worst_t,
            "tolerance": 1e-9, "storm_hits": storm.total_cache_hits,
            "storm_evictions": storm.total_cache_evictions,
            "auditor_checks": tel.auditor.n_checks}


def gate_power_conservation(failures: list[str]) -> dict:
    """Gated-sim energy accounting: the busy/idle/gated/transition buckets
    must sum to the total to 1e-9 and partition every node's horizon —
    gated seconds are never double-charged as idle."""
    from repro.cluster import (ClusterNode, PowerConfig, ReactiveIdlePolicy,
                               ZetaOnlinePolicy, onoff_trace,
                               simulate_cluster)
    from repro.configs import TABLE1
    from repro.core.energy_model import fit_profile
    from repro.energy import SWING_NODE

    fleet = ("llama2-7b", "llama2-13b")
    profiles = {}
    for name in fleet:
        sim = AnalyticLLMSimulator(PAPER_ZOO[name], SWING_NODE, batch=1,
                                   kv_cache=True, noise_sigma=0.0)
        pts = [(8, 8), (64, 64), (256, 128), (512, 512), (128, 32)]
        pbs = [sim.simulate(a, b) for a, b in pts]
        profiles[name] = fit_profile(
            name, TABLE1[name]["a_k"],
            [p[0] for p in pts], [p[1] for p in pts],
            [pb.energy_j for pb in pbs], [pb.runtime_s for pb in pbs])

    trace = onoff_trace(60, 0.5, on_s=5.0, off_s=45.0, seed=3)
    power = PowerConfig(gated_w=8.0, wake_s=10.0, gate_s=4.0,
                        wake_j=500.0, gate_j=100.0)
    nodes = [ClusterNode(i, PAPER_ZOO[name], profiles[name], SWING_NODE,
                         max_batch=8, power=power)
             for i, name in enumerate(fleet)]
    rep = simulate_cluster(
        trace, nodes, ZetaOnlinePolicy(), zeta=0.5,
        autoscaler=ReactiveIdlePolicy(idle_timeout_s=5.0, min_awake=0))
    worst_e = worst_t = 0.0
    if len(rep.records) != len(trace):
        failures.append("power-conservation gate lost requests")
    if rep.total_gates == 0 or rep.total_wakes == 0:
        failures.append("power-conservation gate saw no gate/wake churn")
    for s in rep.node_stats:
        e_sum = (s.busy_energy_j + s.idle_energy_j + s.gated_energy_j
                 + s.transition_energy_j)
        rel_e = abs(e_sum - s.total_energy_j) / max(1.0, s.total_energy_j)
        rel_t = abs(s.accounted_s - s.horizon_s) / max(1.0, s.horizon_s)
        worst_e = max(worst_e, rel_e)
        worst_t = max(worst_t, rel_t)
        if rel_e > 1e-9 or rel_t > 1e-9:
            failures.append(
                f"power conservation violated on node {s.node_id}: "
                f"energy rel {rel_e:.3e}, time rel {rel_t:.3e}")
    total = sum(s.busy_energy_j + s.idle_energy_j + s.gated_energy_j
                + s.transition_energy_j for s in rep.node_stats)
    rel = abs(total - rep.total_energy_j) / max(1.0, rep.total_energy_j)
    if rel > 1e-9:
        failures.append(f"fleet energy buckets off by rel {rel:.3e}")
    return {"worst_energy_rel": max(worst_e, rel), "worst_time_rel": worst_t,
            "tolerance": 1e-9, "gates": rep.total_gates,
            "wakes": rep.total_wakes}


def gate_metrics_overhead(failures: list[str]) -> dict:
    """Full telemetry (metrics + tracer + auditor + periodic sampling) on
    the seeded fig4-style fleet: the ClusterReport must be byte-identical
    to the uninstrumented run, the Prometheus dump must parse, the Chrome
    trace must be valid JSON, every settlement must pass the live auditor
    at 1e-9, and instrumentation CPU overhead must stay ≤ 20%.  (The
    budget was 5% when the uninstrumented loop still re-integrated
    phase physics per fresh fleet; the process-wide memo store removed
    that cost from the denominator, so the same ~25 µs/request of hook
    work now reads as ~10% relative, and the ±5% window-to-window swing
    the null comparison shows on shared runners rides on top — 20% of
    the faster baseline bounds the same absolute cost the old 5% did,
    and a real hook regression still fails every retry window.)"""
    from repro.cluster import (ClusterNode, ReactiveIdlePolicy,
                               SLOPreemptionPolicy, TauOutPredictor,
                               ZetaOnlinePolicy, replay_trace,
                               simulate_cluster)
    from repro.configs import CASE_STUDY_MODELS, TABLE1
    from repro.core.energy_model import fit_profile
    from repro.energy import SWING_NODE
    from repro.obs import (EventTracer, InvariantAuditor, InvariantViolation,
                           Telemetry)

    profiles = {}
    for name in CASE_STUDY_MODELS:
        sim = AnalyticLLMSimulator(PAPER_ZOO[name], SWING_NODE, batch=1,
                                   kv_cache=True, noise_sigma=0.0)
        pts = [(8, 8), (64, 64), (256, 128), (512, 512), (128, 32)]
        pbs = [sim.simulate(a, b) for a, b in pts]
        profiles[name] = fit_profile(
            name, TABLE1[name]["a_k"],
            [p[0] for p in pts], [p[1] for p in pts],
            [pb.energy_j for pb in pbs], [pb.runtime_s for pb in pbs])

    # the fig4 high-rate cell: 8 qps drives real batching and ~20
    # preemption splits, so the auditor's split-energy path is exercised
    # while the baseline per-event work (queue scans, batch scoring) is
    # representative of a loaded fleet
    queries = alpaca_like_workload(WorkloadSpec(n_queries=150, seed=7))
    trace = replay_trace(queries, 8.0, seed=11, name="alpaca@8qps")

    def run(telemetry=None):
        nodes = [ClusterNode(i, PAPER_ZOO[name], profiles[name], SWING_NODE,
                             max_batch=8, dvfs="per_phase")
                 for i, name in enumerate(CASE_STUDY_MODELS)]
        return simulate_cluster(
            trace, nodes,
            ZetaOnlinePolicy(tau_out_predictor=TauOutPredictor()), zeta=0.5,
            autoscaler=ReactiveIdlePolicy(idle_timeout_s=30.0),
            preempter=SLOPreemptionPolicy(slowdown_slo=2.0),
            telemetry=telemetry)

    def full_telemetry():
        return Telemetry(tracer=EventTracer(), auditor=InvariantAuditor(),
                         sample_every_s=5.0)

    # overhead first, on a clean heap (the export checks below allocate
    # MB-scale JSON strings whose allocator churn would pollute the
    # timing).  Interleaved best-of-N on *process* CPU time — a shared
    # runner's wall clock measures the co-tenant, CPU time measures us —
    # with GC paused so collection spikes don't land on one side.  On a
    # steal-prone host even CPU time carries cache-refill noise of a few
    # percent (an off-vs-off null comparison swings ±5%), so a miss is
    # retried with backoff until a quiet window is found: a real
    # regression fails every window, noise doesn't.
    import gc
    budget, rel = 0.20, float("inf")
    us_per_req = float("inf")   # reported for absolute-cost trend reading
    n_requests = len(trace.requests)
    run(); run(full_telemetry())   # warm both paths
    for attempt in range(5):
        if attempt:   # let a transient co-tenant burst pass before retrying
            time.sleep(2 ** attempt)
        reps = 5 + 3 * attempt
        t_off = t_on = float("inf")
        gc.collect()
        gc.disable()
        try:
            for _ in range(reps):
                start = time.process_time()
                run()
                t_off = min(t_off, time.process_time() - start)
                start = time.process_time()
                run(full_telemetry())
                t_on = min(t_on, time.process_time() - start)
        finally:
            gc.enable()
        rel = min(rel, (t_on - t_off) / t_off)
        us_per_req = min(us_per_req, (t_on - t_off) / n_requests * 1e6)
        if rel <= budget:
            break
    if rel > budget:
        failures.append(
            f"telemetry overhead {rel:.1%} ({us_per_req:.1f} µs/request) "
            f"exceeds the {budget:.0%} budget")

    base = run()
    tel = full_telemetry()
    try:
        instr = run(tel)
    except InvariantViolation as exc:
        failures.append(f"live auditor tripped on a clean run: {exc}")
        return {"auditor": "violated"}
    byte_identical = (base.to_json(include_records=True)
                      == instr.to_json(include_records=True))
    if not byte_identical:
        failures.append("telemetry-on report differs from telemetry-off")

    prom = tel.prometheus_text()
    (REPO_ROOT / "BENCH_telemetry.prom").write_text(prom)
    try:
        from prometheus_client.parser import text_string_to_metric_families
        n_fams = len(list(text_string_to_metric_families(prom)))
    except ImportError:   # minimal grammar check without the parser
        n_fams = sum(1 for ln in prom.splitlines()
                     if ln.startswith("# TYPE "))
    if n_fams < 10:
        failures.append(f"prometheus dump looks empty: {n_fams} families")
    try:
        chrome = json.loads(tel.tracer.to_json())
        if not chrome["traceEvents"]:
            failures.append("chrome trace has no events")
    except (json.JSONDecodeError, KeyError) as exc:
        failures.append(f"chrome trace export invalid: {exc}")
    return {"overhead_rel": rel, "budget": budget,
            "overhead_us_per_request": us_per_req,
            "auditor_checks": tel.auditor.n_checks,
            "trace_events": len(tel.tracer.events),
            "prom_families": n_fams,
            "report_byte_identical": byte_identical}


def gate_sharded_replay(failures: list[str]) -> dict:
    """The sharded event engine's two contracts on the fig4 fleet:

    *equivalence* — replaying a seeded fault+autoscale+preemption trace
    over {1, 2, 4, 8} node-group shards is byte-identical to the
    sequential loop (ClusterReport JSON, Prometheus exposition, Chrome
    trace — the merge mode's by-construction guarantee, pinned here
    against drift);

    *throughput* — the engine sustains ≥ 1e6 simulated requests/min,
    measured warm best-of-N over fresh fleets in each execution mode
    (sequential merge, windowed barriers, and the process-pool runner at
    auto worker count); the headline is the best mode, recorded per-mode
    so a single-core runner degrading the pool to inline is visible."""
    from repro.cluster import (ClusterNode, FailoverPolicy, FaultInjector,
                               PowerConfig, ReactiveIdlePolicy,
                               RoundRobinPolicy, Runner, SLOPreemptionPolicy,
                               ZetaOnlinePolicy, replay_trace)
    from repro.configs import CASE_STUDY_MODELS, TABLE1
    from repro.core.energy_model import fit_profile
    from repro.energy import SWING_NODE
    from repro.obs import EventTracer, InvariantAuditor, Telemetry

    profiles = {}
    for name in CASE_STUDY_MODELS:
        sim = AnalyticLLMSimulator(PAPER_ZOO[name], SWING_NODE, batch=1,
                                   kv_cache=True, noise_sigma=0.0)
        pts = [(8, 8), (64, 64), (256, 128), (512, 512), (128, 32)]
        pbs = [sim.simulate(a, b) for a, b in pts]
        profiles[name] = fit_profile(
            name, TABLE1[name]["a_k"],
            [p[0] for p in pts], [p[1] for p in pts],
            [pb.energy_j for pb in pbs], [pb.runtime_s for pb in pbs])

    # --- equivalence: every cross-shard channel live at once ----------
    def governed_nodes():
        return [ClusterNode(i, PAPER_ZOO[name], profiles[name], SWING_NODE,
                            max_batch=2,
                            power=PowerConfig(wake_s=3.0, gate_s=1.0))
                for i, name in enumerate(CASE_STUDY_MODELS * 2)]

    eq_trace = replay_trace(
        alpaca_like_workload(WorkloadSpec(n_queries=100, seed=7)),
        6.0, seed=11, name="alpaca@6qps")
    faults = FaultInjector(mttf_s=15.0, mttr_s=4.0, seed=5).generate(
        [n.node_id for n in governed_nodes()], eq_trace.duration_s + 20)

    def replay(shards):
        tel = Telemetry(tracer=EventTracer(), auditor=InvariantAuditor(),
                        sample_every_s=2.0)
        rep = Runner(eq_trace, governed_nodes(),
                     FailoverPolicy(ZetaOnlinePolicy()), zeta=0.5,
                     autoscaler=ReactiveIdlePolicy(idle_timeout_s=2.0),
                     preempter=SLOPreemptionPolicy(slowdown_slo=1.2,
                                                   min_remaining=2),
                     faults=faults, telemetry=tel, shard_count=shards).run()
        return (rep.to_json(include_records=True), tel.prometheus_text(),
                tel.tracer.to_json())

    base = replay(1)
    equivalent_at = []
    for k in (2, 4, 8):
        if replay(k) == base:
            equivalent_at.append(k)
        else:
            failures.append(
                f"sharded replay diverged from sequential at shards={k}")

    # --- throughput: the fig4 fleet, warm best-of-N per mode ----------
    def fleet():
        return [ClusterNode(i, PAPER_ZOO[name], profiles[name], SWING_NODE,
                            max_batch=8)
                for i, name in enumerate(CASE_STUDY_MODELS)]

    n_requests = 1200
    tp_trace = replay_trace(
        alpaca_like_workload(WorkloadSpec(n_queries=n_requests, seed=7)),
        8.0, seed=11, name="alpaca@8qps")

    def throughput(mode, shards, workers, reps=3):
        best = float("inf")
        for _ in range(reps):
            nodes = fleet()
            start = time.perf_counter()
            Runner(tp_trace, nodes, RoundRobinPolicy(), zeta=0.5,
                   shard_count=shards, mode=mode, workers=workers).run()
            best = min(best, time.perf_counter() - start)
        return n_requests / best * 60.0

    throughput("merge", 1, None, reps=1)   # warm the physics memos
    modes = {
        "merge_s1": throughput("merge", 1, None),
        "windowed_s4": throughput("windowed", 4, None),
        "pooled_s4_auto": throughput("windowed", 4, "auto"),
    }
    headline_mode = max(modes, key=modes.get)
    requests_per_min = modes[headline_mode]
    floor = 1e6
    if requests_per_min < floor:
        failures.append(
            f"sharded engine sustains {requests_per_min:,.0f} simulated "
            f"requests/min (best mode {headline_mode}) — below the "
            f"{floor:,.0f} floor")
    return {"equivalent_at_shards": equivalent_at,
            "requests_per_min": requests_per_min,
            "headline_mode": headline_mode,
            "requests_per_min_by_mode": modes,
            "floor": floor,
            "auto_workers": min(4, os.cpu_count() or 1)}


def run_gates(quick: bool) -> tuple[dict, list[str]]:
    failures: list[str] = []
    out = {
        "decode_closed_form": gate_decode_closed_form(failures),
        "pass_costs_batch": gate_pass_costs_batch(failures),
        "measure_batch": gate_measure_batch(failures),
        "capacitated_solver": gate_capacitated_solver(
            failures, n_instances=8 if quick else 12),
        "warm_start": gate_warm_start(
            failures, n_instances=12 if quick else 25),
        "dvfs_closed_form": gate_dvfs_closed_form(failures),
        "power_conservation": gate_power_conservation(failures),
        "preemption_split": gate_preemption_split(failures),
        "migration_settlement": gate_migration_settlement(failures),
        "checkpoint_settlement": gate_checkpoint_settlement(failures),
        "prefix_cache_settlement": gate_prefix_cache_settlement(failures),
        "metrics_overhead": gate_metrics_overhead(failures),
        "sharded_replay": gate_sharded_replay(failures),
    }
    return out, failures


# ---------------------------------------------------------------------------
# Timings (full run only)
# ---------------------------------------------------------------------------


def bench_decode() -> dict:
    """Headline (a): decode_cost closed form at τout = 4096 vs the loop."""
    cfg = PAPER_ZOO["llama2-7b"]
    out = {}
    for kv in (False, True):
        sim = AnalyticLLMSimulator(cfg, batch=32, kv_cache=kv, noise_sigma=0.0)
        us_closed, res_c = timed(
            lambda: sim._decode_closed_form(32, 4096, 32), repeats=20)
        us_exact, res_e = timed(
            lambda: sim.decode_cost_chunked(32, 4096, chunk=1), repeats=2)
        us_256, res_256 = timed(
            lambda: sim.decode_cost_chunked(32, 4096, chunk=256), repeats=10)
        rel_exact = max(abs(res_c[0] - res_e[0]) / res_e[0],
                        abs(res_c[1] - res_e[1]) / res_e[1])
        rel_256 = max(abs(res_256[0] - res_e[0]) / res_e[0],
                      abs(res_256[1] - res_e[1]) / res_e[1])
        out[f"kv_{'on' if kv else 'off'}"] = {
            "closed_form_us": us_closed,
            "exact_loop_us": us_exact,
            "chunk256_loop_us": us_256,
            "speedup_vs_exact_loop": us_exact / us_closed,
            "speedup_vs_chunk256": us_256 / us_closed,
            "rel_err_vs_exact_loop": rel_exact,
            "chunk256_rel_err_vs_exact": rel_256,
        }
    return out


def bench_pass_costs_batch(sizes: list[int]) -> dict:
    cfg = PAPER_ZOO["llama2-7b"]
    out = {}
    for m in sizes:
        rng = np.random.default_rng(m)
        nt = rng.integers(1, 2048, m).astype(float)
        ctx = nt.copy()
        us_batch, pcb = timed(
            lambda: costs_lib.pass_costs_batch(cfg, nt, ctx, 32.0,
                                               decode=False), repeats=5)
        n_scalar = min(m, 2000)  # scalar loop timed on a slice, scaled up
        us_scalar_slice, _ = timed(
            lambda: [costs_lib.pass_costs(cfg, nt[i], ctx[i], 32.0,
                                          decode=False)
                     for i in range(n_scalar)], repeats=2)
        us_scalar = us_scalar_slice * (m / n_scalar)
        out[str(m)] = {
            "batch_us": us_batch,
            "scalar_loop_us": us_scalar,
            "speedup": us_scalar / us_batch,
        }
    return out


def bench_measure_batch(sizes: list[int]) -> dict:
    cfg = PAPER_ZOO["llama2-7b"]
    out = {}
    for m in sizes:
        qs = workload(m, seed=m)
        tin = np.array([q[0] for q in qs])
        tout = np.array([q[1] for q in qs])
        sim_b = AnalyticLLMSimulator(cfg, kv_cache=True, seed=0)
        t0 = time.perf_counter()
        sim_b.measure_batch(tin, tout)
        t_batch = time.perf_counter() - t0
        n_seq = min(m, 1000)
        sim_s = AnalyticLLMSimulator(cfg, kv_cache=True, seed=0)
        t0 = time.perf_counter()
        for i in range(n_seq):
            sim_s.measure(int(tin[i]), int(tout[i]))
        t_seq = (time.perf_counter() - t0) * (m / n_seq)
        out[str(m)] = {
            "batch_s": t_batch,
            "sequential_s_scaled": t_seq,
            "speedup": t_seq / t_batch,
            "unique_pairs": int(len(np.unique(np.stack([tin, tout], 1),
                                              axis=0))),
        }
    return out


def bench_campaign() -> dict:
    """Whole-grid batched characterization campaign vs the scalar driver."""
    cfg = PAPER_ZOO["llama2-7b"]
    settings = characterize_lib.CampaignSettings(max_trials=5)
    sim_b = AnalyticLLMSimulator(cfg, kv_cache=False, seed=0)
    t0 = time.perf_counter()
    trials_b = characterize_lib.run_campaign(
        "llama2-7b", None, settings, measure_batch=sim_b.measure_batch)
    t_batch = time.perf_counter() - t0
    sim_s = AnalyticLLMSimulator(cfg, kv_cache=False, seed=0)
    t0 = time.perf_counter()
    trials_s = characterize_lib.run_campaign("llama2-7b", sim_s.measure,
                                             settings)
    t_seq = time.perf_counter() - t0
    return {
        "batched_s": t_batch,
        "sequential_s": t_seq,
        "speedup": t_seq / t_batch,
        "trials_batched": len(trials_b),
        "trials_sequential": len(trials_s),
    }


def bench_schedule(sizes: list[int]) -> dict:
    out = {}
    profs = synthetic_fleet(5, seed=1)
    for m in sizes:
        qs = workload(m, seed=m)
        us, asg = timed(lambda: scheduler.schedule(profs, qs, 0.5), repeats=3)
        out[str(m)] = {"schedule_us": us,
                       "queries_per_s": m / (us * 1e-6),
                       "objective": asg.objective}
    return out


def bench_schedule_capacitated(sizes: list[int], headline_m: int,
                               ref_direct_max: int,
                               failures: list[str]) -> dict:
    """Headline (b): chains solver vs the _MinCostFlow oracle.

    The oracle is O(m²k), so it is run directly up to `ref_direct_max`
    (objectives checked bit-identical at every direct point) and its
    headline-size runtime is extrapolated from a power-law fit; the chains
    result at the headline size carries the exact optimality certificate
    instead of an oracle re-solve."""
    k = 5
    profs = synthetic_fleet(k, seed=1)
    rng = np.random.default_rng(42)
    gamma = random_gamma(k, rng)
    zeta = 0.5

    direct_ms = sorted({m for m in (500, 1000, 2000, 5000, ref_direct_max)
                        if m <= ref_direct_max})
    if len(direct_ms) < 2:  # the power-law fit needs >= 2 direct points
        direct_ms = sorted({max(2, ref_direct_max // 4), ref_direct_max})
    if len(direct_ms) < 2:
        raise SystemExit("--ref-direct-max too small to fit the oracle "
                         "runtime (need >= 2 distinct direct sizes)")
    points = {}
    for m in direct_ms:
        qs = workload(m, seed=m)
        t0 = time.perf_counter()
        a = scheduler.schedule_capacitated(profs, qs, zeta, gamma,
                                           method="chains")
        t_chain = time.perf_counter() - t0
        t0 = time.perf_counter()
        b = scheduler.schedule_capacitated(profs, qs, zeta, gamma,
                                           method="flow")
        t_flow = time.perf_counter() - t0
        identical = a.objective == b.objective
        if not identical and abs(a.objective - b.objective) > 1e-12 * max(
                1.0, abs(b.objective)):
            failures.append(
                f"capacitated objective mismatch at m={m}: "
                f"chains={a.objective!r} flow={b.objective!r}")
        points[str(m)] = {
            "chains_s": t_chain,
            "flow_s": t_flow,
            "speedup": t_flow / t_chain,
            "objective_bit_identical": identical,
        }

    # power-law fit of the oracle runtime (known ~quadratic in m)
    ms = np.array([int(m) for m in points], dtype=float)
    ts = np.array([points[m]["flow_s"] for m in points])
    slope, intercept = np.polyfit(np.log(ms), np.log(ts), 1)
    flow_headline_s = float(np.exp(intercept + slope * np.log(headline_m)))

    qs = workload(headline_m, seed=headline_m)
    t0 = time.perf_counter()
    a = scheduler.schedule_capacitated(profs, qs, zeta, gamma,
                                       method="chains")
    t_chain_headline = time.perf_counter() - t0
    costs = normalized_costs(profs, qs)
    C = objective_matrix(costs, zeta)
    caps = scheduler._capacities_from_gamma(gamma, len(qs))
    cert = scheduler.capacitated_optimality_certificate(C, a.assignee, caps)
    if not cert:
        failures.append(f"optimality certificate failed at m={headline_m}")

    extra_sizes = {}
    for m in sizes:
        if str(m) in points or m == headline_m:
            continue
        qs_m = workload(m, seed=m)
        t0 = time.perf_counter()
        scheduler.schedule_capacitated(profs, qs_m, zeta, gamma,
                                       method="chains")
        extra_sizes[str(m)] = {"chains_s": time.perf_counter() - t0}

    return {
        "k": k,
        "direct_comparison": points,
        "flow_runtime_fit": {"log_slope": float(slope),
                             "log_intercept": float(intercept)},
        "headline": {
            "m": headline_m,
            "chains_s": t_chain_headline,
            "flow_s_extrapolated": flow_headline_s,
            "speedup_vs_flow_extrapolated": flow_headline_s / t_chain_headline,
            "optimality_certificate": cert,
            "objective": a.objective,
        },
        "chains_scaling": extra_sizes,
    }


def bench_warm_start(headline_m: int, failures: list[str],
                     *, delta: int = 64) -> dict:
    """Headline (c): warm-start small-delta reschedule vs cold chains
    re-solve at the headline size.  The delta draws from the same workload
    distribution, so the normalization maxima stay put and the repair does
    O(delta) chain moves — the small-delta regime the ≥10× target names.
    A ζ-step re-plan (the sweep's inner move) is timed too."""
    k = 5
    profs = synthetic_fleet(k, seed=1)
    gamma = tuple((np.ones(k) / k).tolist())
    qs = workload(headline_m, seed=headline_m)
    inc = IncrementalScheduler(profs, qs, 0.5, gamma)
    added = workload(delta, seed=headline_m + 1)
    rng = np.random.default_rng(3)
    removed = list(rng.choice(inc.active_ids, size=delta, replace=False))

    t0 = time.perf_counter()
    warm = inc.reschedule(added=added, removed=removed)
    t_warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    cold = scheduler.schedule_capacitated(profs, inc.active_queries(),
                                          0.5, gamma)
    t_cold = time.perf_counter() - t0
    delta_match = abs(warm.objective - cold.objective) <= 1e-12 * max(
        1.0, abs(cold.objective))
    if not delta_match:
        failures.append(
            f"warm-start headline objective mismatch at m={headline_m}: "
            f"warm={warm.objective!r} cold={cold.objective!r}")

    t0 = time.perf_counter()
    zstep = inc.reschedule(zeta=0.55)
    t_zeta = time.perf_counter() - t0
    cold_z = scheduler.schedule_capacitated(profs, inc.active_queries(),
                                            0.55, gamma)
    zeta_match = abs(zstep.objective - cold_z.objective) <= 1e-12 * max(
        1.0, abs(cold_z.objective))
    if not zeta_match:
        failures.append(f"warm-start ζ-step mismatch at m={headline_m}")
    return {
        "m": headline_m,
        "delta": delta,
        "warm_reschedule_s": t_warm,
        "cold_chains_s": t_cold,
        "speedup": t_cold / t_warm,
        "zeta_step_warm_s": t_zeta,
        "objective_matches_cold": delta_match and zeta_match,
    }


def bench_pareto(sizes: list[int], failures: list[str]) -> dict:
    """Streaming ζ sweep: warm grid vs cold zeta_sweep, and the exact
    breakpoint frontier's cost."""
    k = 5
    profs = synthetic_fleet(k, seed=1)
    gamma = tuple((np.ones(k) / k).tolist())
    zetas = np.linspace(0.0, 1.0, 21)
    out = {}
    for m in sizes:
        if m > 20000:   # cold sweep at 21 ζ would dominate the suite
            continue
        qs = workload(m, seed=m)
        t0 = time.perf_counter()
        warm = pareto_frontier(profs, qs, zetas, gamma=gamma)
        t_warm = time.perf_counter() - t0
        t0 = time.perf_counter()
        cold = scheduler.zeta_sweep(profs, qs, zetas, gamma=gamma)
        t_cold = time.perf_counter() - t0
        match = all(abs(a.objective - b.objective)
                    <= 1e-12 * max(1.0, abs(b.objective))
                    for a, b in zip(warm.assignments, cold))
        if not match:
            failures.append(f"pareto grid objective mismatch at m={m}")
        t0 = time.perf_counter()
        fr = pareto_frontier(profs, qs, breakpoints=True)
        t_bp = time.perf_counter() - t0
        out[str(m)] = {
            "grid21_warm_s": t_warm,
            "grid21_cold_s": t_cold,
            "grid21_speedup": t_cold / t_warm,
            "grid21_objectives_match": match,
            "breakpoints": len(fr.breakpoints),
            "breakpoint_frontier_s": t_bp,
        }
    return out


def bench_cluster(sizes: list[int]) -> dict:
    from repro.cluster import (ClusterNode, ZetaOnlinePolicy, poisson_trace,
                               simulate_cluster)
    from repro.configs import TABLE1
    from repro.core.energy_model import fit_profile
    from repro.energy import SWING_NODE

    fleet = ("llama2-7b", "llama2-13b", "llama2-70b")
    profiles = {}
    for name in fleet:
        sim = AnalyticLLMSimulator(PAPER_ZOO[name], SWING_NODE, batch=1,
                                   kv_cache=True, noise_sigma=0.0)
        pts = [(8, 8), (64, 64), (256, 128), (1024, 256), (32, 512),
               (512, 512), (128, 32), (2048, 64)]
        pbs = [sim.simulate(a, b) for a, b in pts]
        profiles[name] = fit_profile(
            name, TABLE1[name]["a_k"],
            [p[0] for p in pts], [p[1] for p in pts],
            [pb.energy_j for pb in pbs], [pb.runtime_s for pb in pbs])

    out = {}
    for n in sizes:
        if n > 20000:   # event loop is O(n log n); keep the suite bounded
            continue
        trace = poisson_trace(n, 8.0, seed=3)
        nodes = [ClusterNode(i, PAPER_ZOO[name], profiles[name], SWING_NODE,
                             max_batch=8) for i, name in enumerate(fleet)]
        t0 = time.perf_counter()
        rep = simulate_cluster(trace, nodes, ZetaOnlinePolicy(), zeta=0.5)
        dt = time.perf_counter() - t0
        out[str(n)] = {"wall_s": dt, "requests_per_s": n / dt,
                       "slo": rep.slo_attainment()}
    return out


# ---------------------------------------------------------------------------


def _git_commit() -> str:
    import subprocess
    try:
        out = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             cwd=REPO_ROOT, capture_output=True, text=True,
                             timeout=10)
        return out.stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001
        return "unknown"


def _load_history(path: Path) -> list:
    """Prior runs' compact entries — the perf trajectory across PRs."""
    if not path.exists():
        return []
    try:
        prev = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError):
        return []
    history = list(prev.get("history", []))
    if not history and "headline" in prev:
        # first run after the history feature landed: preserve the last
        # pre-history snapshot as the opening entry
        history.append({"commit": "pre-history",
                        "created_unix": prev.get("created_unix"),
                        "wall_s": prev.get("wall_s"),
                        "headline": prev["headline"]})
    return history


def _merge_history(history: list, entry: dict) -> list:
    """One history entry per commit: a re-run on the same commit replaces
    its entry in place (keeping whichever run had the best wall_s), so
    repeated local runs don't inflate the trajectory; prior commits'
    entries are never touched."""
    out = list(history)
    for i, prev in enumerate(out):
        if prev.get("commit") == entry.get("commit"):
            prev_wall = prev.get("wall_s") or float("inf")
            new_wall = entry.get("wall_s") or float("inf")
            out[i] = entry if new_wall <= prev_wall else prev
            return out
    out.append(entry)
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="correctness gates only (the scripts/test.sh perf tier)")
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_core.json"))
    ap.add_argument("--sizes", default="1000,10000,100000")
    ap.add_argument("--headline-m", type=int, default=50_000)
    ap.add_argument("--ref-direct-max", type=int, default=10_000)
    args = ap.parse_args(argv)
    sizes = [int(s) for s in args.sizes.split(",") if s]

    t_start = time.time()
    gates, failures = run_gates(args.quick)
    for name, res in gates.items():
        print(f"gate.{name},0,{res}")

    if not args.quick:
        bench = {
            "decode_cost_tau4096": bench_decode(),
            "pass_costs_batch": bench_pass_costs_batch(sizes),
            "measure_batch": bench_measure_batch(sizes),
            "campaign_grid": bench_campaign(),
            "schedule": bench_schedule(sizes),
            "schedule_capacitated": bench_schedule_capacitated(
                sizes, args.headline_m, args.ref_direct_max, failures),
            "warm_start_reschedule": bench_warm_start(
                args.headline_m, failures),
            "pareto_sweep": bench_pareto(sizes, failures),
            "cluster_sim": bench_cluster(sizes),
        }
        dec = bench["decode_cost_tau4096"]["kv_off"]
        cap = bench["schedule_capacitated"]["headline"]
        ws = bench["warm_start_reschedule"]
        doc = {
            "suite": "core",
            "created_unix": time.time(),
            "wall_s": time.time() - t_start,
            "headline": {
                "decode_cost_tau4096_speedup_vs_exact_loop":
                    dec["speedup_vs_exact_loop"],
                "decode_cost_tau4096_rel_err": dec["rel_err_vs_exact_loop"],
                f"schedule_capacitated_m{args.headline_m}_k5_speedup":
                    cap["speedup_vs_flow_extrapolated"],
                f"schedule_capacitated_m{args.headline_m}_chains_s":
                    cap["chains_s"],
                f"schedule_capacitated_m{args.headline_m}_flow_s_extrapolated":
                    cap["flow_s_extrapolated"],
                "objectives_bit_identical_at_direct_points": all(
                    p["objective_bit_identical"] for p in
                    bench["schedule_capacitated"]["direct_comparison"].values()),
                "optimality_certificate_at_headline":
                    cap["optimality_certificate"],
                f"warm_start_reschedule_m{args.headline_m}_delta{ws['delta']}"
                "_speedup": ws["speedup"],
                f"warm_start_reschedule_m{args.headline_m}_warm_s":
                    ws["warm_reschedule_s"],
                "warm_start_objective_matches_cold":
                    ws["objective_matches_cold"],
                "sharded_replay_requests_per_min":
                    gates["sharded_replay"]["requests_per_min"],
                "sharded_replay_equivalent_at_shards":
                    gates["sharded_replay"]["equivalent_at_shards"],
            },
            "gates": gates,
            "bench": bench,
            "env": {"python": sys.version.split()[0],
                    "numpy": np.__version__},
        }
        out_path = Path(args.out)
        doc["history"] = _merge_history(_load_history(out_path), {
            "commit": _git_commit(),
            "created_unix": doc["created_unix"],
            "wall_s": doc["wall_s"],
            "headline": doc["headline"],
        })
        Path(args.out).write_text(json.dumps(doc, indent=2) + "\n")
        print(f"perf_suite.wrote,{(time.time() - t_start) * 1e6:.0f},{args.out}")
        for key, val in doc["headline"].items():
            print(f"headline.{key},0,{val}")

    if failures:
        for f in failures:
            print(f"FAIL,0,{f}", file=sys.stderr)
        return 1
    print(f"perf_suite.ok,{(time.time() - t_start) * 1e6:.0f},"
          f"{'quick' if args.quick else 'full'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

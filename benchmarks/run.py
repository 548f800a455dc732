# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark driver.

``us_per_call`` is the per-inference (or per-task) latency of the measured
configuration; ``derived`` is that table's headline metric vs the paper.

``--gate NAME`` instead runs a CI gate (benchmarks/ci_gates.py) with the
exact assertions the workflow uses — see ``python -m benchmarks.ci_gates``.
"""
from __future__ import annotations


def main() -> None:
    from benchmarks import (fig2_tradeoff, fig3_weight_sweep, fleet_scale,
                            obs_overhead, overhead, partition_scale,
                            sim_serving, table2_carbon_footprint,
                            table4_multi_model, table5_node_distribution,
                            temporal_shifting, tenancy_saturation)

    rows = []

    t2 = table2_carbon_footprint.run()
    rows.append(("table2_green_carbon_reduction",
                 t2["ce-green"]["latency_ms"] * 1e3,
                 f"reduction_pct={t2['ce-green']['reduction_vs_mono_pct']:.1f}"))

    t4 = table4_multi_model.run()
    for model, r in t4.items():
        rows.append((f"table4_{model}", r["green_latency_ms"] * 1e3,
                     f"reduction_pct={r['reduction_pct']:.1f}"))

    t5 = table5_node_distribution.run()
    rows.append(("table5_green_node_share", 0.0,
                 f"green_mode_green_node_pct={t5['green']['node-green']:.0f}"))

    f2 = fig2_tradeoff.run()
    rows.append(("fig2_carbon_efficiency",
                 f2["ce-green"]["latency_ms"] * 1e3,
                 f"improvement_x={f2['improvement_x']:.2f}"))

    f3 = fig3_weight_sweep.run()
    rows.append(("fig3_weight_sweep", 0.0,
                 f"transition_w_c={f3['transition_w_c']}"))

    ov = overhead.run()
    rows.append(("scheduler_overhead_per_task", ov["per_task_ms"] * 1e3,
                 "paper_us=30"))
    rows.append(("scheduler_vectorised_100k_nodes", ov["vector_100k_nodes_us"],
                 f"ns_per_node={ov['vector_ns_per_node']:.1f}"))

    fs = fleet_scale.run()
    top = max(fs["select"], key=lambda r: (r["n_nodes"], r["batch"]))
    rows.append((f"fleet_scale_{top['n_nodes']}n_{top['batch']}b_per_task",
                 top["cached_per_task_ms"] * 1e3,
                 f"speedup_vs_rebuild_x={top['speedup_x']:.0f}"))
    wk = max(fs["plan_wake"], key=lambda r: r["n_nodes"])
    rows.append((f"fleet_scale_plan_wake_{wk['n_nodes']}n",
                 wk["batched_ms"] * 1e3,
                 f"speedup_vs_scalar_x={wk['speedup_x']:.0f}"))
    se = max(fs["step"], key=lambda r: (r["n_nodes"], r["batch"]))
    rows.append((f"fleet_scale_step_e2e_{se['n_nodes']}n_{se['batch']}b",
                 se["batched_per_task_ms"] * 1e3,
                 f"speedup_vs_task_loop_x={se['speedup_x']:.1f}"))

    ts = temporal_shifting.run(deadlines=(16.0,))
    rows.append(("beyond_paper_temporal_shifting", 0.0,
                 f"savings_pct={ts[0]['savings_pct']:.1f}"))

    sim = sim_serving.run()
    acc = next(r for r in sim["deferral"] if r["bias_h"] == 0.0)
    worst = sim["deferral"][-1]
    rows.append(("sim_serving_deferral_accurate", 0.0,
                 f"savings_pct={acc['savings_vs_run_now_pct']:.1f}"))
    rows.append(("sim_serving_forecast_regret", 0.0,
                 f"regret_g_at_{worst['bias_h']:g}h={worst['regret_g']:.4f}"))
    loaded = max((r for r in sim["rate_mode"] if r["mode"] == "green"),
                 key=lambda r: r["rate_per_hour"])
    rows.append(("sim_serving_green_wait_p95",
                 loaded["wait_s_p95"] * 1e6,
                 f"slo_violation_rate={loaded['slo_violation_rate']:.3f}"))

    tn = tenancy_saturation.run()
    ov_t = max(tn["overhead"], key=lambda r: (r["n_nodes"], r["batch"]))
    rows.append((f"tenancy_step_e2e_{ov_t['n_nodes']}n_{ov_t['batch']}b",
                 ov_t["tenancy_per_task_ms"] * 1e3,
                 f"admission_overhead_us={ov_t['admission_overhead_us_per_task']:.2f}"))
    sat = max(tn["saturation"],
              key=lambda r: (r["clients_per_tenant"], -r["allowance_scale"]))
    rows.append(("tenancy_saturation_fairness", 0.0,
                 f"jain={sat['budget_fairness_jain']:.3f}"))

    pt = partition_scale.run()
    pstep = max(pt["step"], key=lambda r: (r["n_nodes"], r["batch"],
                                           r["cuts"]))
    rows.append((f"partition_step_e2e_{pstep['n_nodes']}n_{pstep['batch']}b"
                 f"_{pstep['cuts']}p",
                 pstep["per_task_ms"] * 1e3,
                 f"vs_paper_budget_x={pstep['vs_paper_x']:.2f}"))
    rows.append(("partition_conformal_coverage", 0.0,
                 f"heldout={pt['conformal']['heldout_coverage']:.3f}"))

    ob = obs_overhead.run()
    acc_row = max(ob["rows"], key=lambda r: (r["n_nodes"] == 10_000,
                                             r["n_nodes"], r["batch"]))
    rows.append((f"obs_enabled_step_{acc_row['n_nodes']}n"
                 f"_{acc_row['batch']}b",
                 acc_row["enabled_per_task_ms"] * 1e3,
                 f"overhead_x={acc_row['overhead_x']:.2f}"))

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser(description="benchmark / CI gate driver")
    parser.add_argument("--gate", default=None,
                        help="run a CI gate from benchmarks.ci_gates "
                             "('overhead', 'fleet', 'sim', 'tenancy', "
                             "'partition', 'obs', 'trend', 'all') instead "
                             "of the benchmark CSV")
    parser.add_argument("--baseline", default=None,
                        help="baseline BENCH_fleet_scale.json for --gate trend")
    cli = parser.parse_args()
    if cli.gate is not None:
        from benchmarks import ci_gates

        ci_gates.main(gate=cli.gate, baseline=cli.baseline)
    else:
        main()

"""Render the EXPERIMENTS.md §Dry-run / §Roofline / §Coaxial tables.

    PYTHONPATH=src python -m benchmarks.report [--mesh 16x16]

Markdown to stdout; EXPERIMENTS.md embeds the output.  The §Coaxial table
is sliced from the one shared design-space sweep (a single XLA compile for
every design x latency x core-count cell).
"""

import argparse
import json

from benchmarks.roofline import analyze, load_cells, model_flops
from repro.configs import ARCHS, SHAPES


def dryrun_table(mesh: str) -> str:
    cells = {(c["arch"], c["shape"]): c for c in load_cells(mesh)}
    lines = [
        f"| arch | shape | status | compile s | HLO GFLOP/chip | "
        f"HBM GB/chip | coll GB/chip | temp GiB/chip |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for arch in ARCHS:
        for shape in SHAPES:
            c = cells.get((arch, shape.name))
            if c is None:
                lines.append(f"| {arch} | {shape.name} | missing | | | | | |")
                continue
            if c["status"] != "ok":
                reason = c["status"].replace("skip: ", "")
                lines.append(
                    f"| {arch} | {shape.name} | SKIP ({reason[:42]}) "
                    f"| | | | | |")
                continue
            temp = c["memory"].get("temp_bytes", 0) / 2**30
            lines.append(
                f"| {arch} | {shape.name} | ok | {c['seconds']:.0f} | "
                f"{c['flops_per_chip']/1e9:.0f} | "
                f"{c['bytes_per_chip']/1e9:.1f} | "
                f"{c['collectives']['total']/1e9:.2f} | {temp:.1f} |")
    return "\n".join(lines)


def roofline_table(mesh: str) -> str:
    cells = {(c["arch"], c["shape"]): c for c in load_cells(mesh)}
    lines = [
        "| arch | shape | compute ms | memory ms | collective ms | "
        "dominant | useful ratio | roofline frac |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for arch in ARCHS:
        for shape in SHAPES:
            c = cells.get((arch, shape.name))
            if c is None or c["status"] != "ok":
                continue
            t = analyze(c)
            lines.append(
                f"| {arch} | {shape.name} | {t['compute_s']*1e3:.2f} | "
                f"{t['memory_s']*1e3:.2f} | {t['collective_s']*1e3:.2f} | "
                f"{t['dominant'].replace('_s','')} | "
                f"{t['useful_ratio']:.2f} | {t['mfu_bound']:.3f} |")
    return "\n".join(lines)


def variant_table(arch: str, shape: str, mesh: str = "16x16") -> str:
    """All recorded variants of one cell (the §Perf iteration log)."""
    rows = []
    import glob
    import os
    from benchmarks.roofline import RESULTS_DIR
    for path in sorted(glob.glob(os.path.join(
            RESULTS_DIR, f"{arch}__{shape}__{mesh}__*.json"))):
        with open(path) as f:
            rows.append(json.load(f))
    lines = [
        "| variant | GFLOP/chip | HBM GB/chip | coll GB/chip | "
        "temp GiB | dominant | bound ms |",
        "|---|---|---|---|---|---|---|",
    ]
    for c in rows:
        if c["status"] != "ok":
            lines.append(f"| {c['variant']} | error: {c['error'][:60]} "
                         f"| | | | | |")
            continue
        t = analyze(c)
        lines.append(
            f"| {c['variant']} | {c['flops_per_chip']/1e9:.0f} | "
            f"{c['bytes_per_chip']/1e9:.1f} | "
            f"{c['collectives']['total']/1e9:.2f} | "
            f"{c['memory'].get('temp_bytes',0)/2**30:.1f} | "
            f"{t['dominant'].replace('_s','')} | {t['bound_s']*1e3:.2f} |")
    return "\n".join(lines)


def coaxial_table() -> str:
    """Geomean speedup vs baseline for every registered design, at both
    §6.4 latency points and every §6.5 core count -- one sweep, one table."""
    from repro.core import coaxial
    sw = coaxial.default_sweep()
    gm = sw.geomean_grid()          # (D, L, C)
    lat_labels = ["default" if l is None else f"{l:.0f}ns"
                  for l in sw.iface_lats]
    header = ["design"] + [f"{lab} @{c}c" for lab in lat_labels
                           for c in sw.cores]
    lines = ["| " + " | ".join(header) + " |",
             "|" + "---|" * len(header)]
    for i, d in enumerate(sw.designs):
        if d.name == sw.baseline_name:
            continue
        cells = [f"{gm[i, j, k]:.3f}" for j in range(len(sw.iface_lats))
                 for k in range(len(sw.cores))]
        lines.append("| " + " | ".join([d.name] + cells) + " |")
    return "\n".join(lines)


def drift_table() -> str:
    """Closed-form vs memsim-backed headline numbers, one row per
    headline -- the "mechanism replaces closed form" drift experiment."""
    from benchmarks.drift_headline import drift_rows, drift_sweep
    lines = ["| headline | closed form | memsim-backed | drift |",
             "|---|---|---|---|"]
    for r in drift_rows(drift_sweep()):
        lines.append(f"| {r['metric']} | {r['closed']:.3f} | "
                     f"{r['memsim']:.3f} | {r['drift_pct']:+.1f}% |")
    return "\n".join(lines)


def harvest_table() -> str:
    """The idle-I/O harvesting frontier (duty x channels) and the
    backend drift on a harvesting design -- the 2511.12349 rows."""
    from benchmarks.harvest_headline import drift_row, frontier_rows, \
        frontier_sim, headline
    rows = frontier_rows(frontier_sim())
    lines = ["| channels | duty | queuing mean ns | mean reduction | "
             "p99 reduction |",
             "|---|---|---|---|---|"]
    for r in rows:
        lines.append(
            f"| {r['channels']} | {r['duty']:g} | "
            f"{r['q_mean0_ns']:.0f} -> {r['q_mean_ns']:.0f} | "
            f"x{r['mean_reduction']:.2f} | x{r['p99_reduction']:.2f} |")
    h = headline(rows)
    d = drift_row()
    lines += ["",
              f"Headline: geomean x{h['reduction_gm']:.2f}, max "
              f"x{h['reduction_max']:.2f} queuing-delay reduction "
              f"(paper: 1.52x mean / ~3x max).",
              f"Backend drift on coaxial-4x+harvest: closed form "
              f"{d['closed']:.3f} vs memsim {d['memsim']:.3f} geomean "
              f"speedup ({d['drift_pct']:+.1f}%).",
              f"Harvest gain (memsim backend): coaxial-4x "
              f"{d['memsim_plain']:.3f} -> {d['memsim']:.3f} "
              f"({d['gain_pct']:+.1f}% -- the headline the closed form "
              f"cannot see)."]
    return "\n".join(lines)


def pareto_table() -> str:
    """The channels x LLC area-vs-speedup frontier (named-axis sweep),
    knee point flagged -- the design the frontier says to buy."""
    from benchmarks.pareto_frontier import frontier_sweep, knee_point
    sw = frontier_sweep()
    front = sw.pareto(cost="rel_area")
    knee = knee_point(front)
    lines = ["| design | llc MB/core | rel area | rel pins | geomean "
             "speedup | |",
             "|---|---|---|---|---|---|"]
    for p in front:
        mark = "knee" if p is knee else ""
        lines.append(
            f"| {p['design']} | {p['llc_mb_per_core']:g} | "
            f"{p['rel_area']:.3f} | {p['rel_pins']:.3f} | "
            f"{p['geomean_speedup']:.3f} | {mark} |")
    return "\n".join(lines)


def serving_table(arch: str = "stablelm-1.6b",
                  slo_p99_ms: float = 500.0) -> str:
    """Every capacity-planner verdict for one serving scenario, the
    SLO-meeting minimum-area pick flagged -- the repro.serving answer."""
    from benchmarks.common import des_budget, des_engine
    from repro.serving import capacity, traffic
    trace = traffic.synthetic_diurnal(n_epochs=4)
    engine = des_engine("event")
    plan = capacity.plan_capacity(
        (arch,), trace, slo_p99_ms=slo_p99_ms, peak_util=0.65,
        steps=des_budget(capacity.DEFAULT_STEPS, engine), engine=engine)
    lines = [f"Scenario: {arch} @ batch {plan.batch} / context "
             f"{plan.context}, trace `{plan.trace}`, SLO p99 <= "
             f"{plan.slo_p99_ms:g} ms ({plan.engine} engine, "
             f"{plan.steps} ns/cell)", "",
             "| design | tier split | rel area | rel pins | peak rho | "
             "access p99 ns | token p99 ms | SLO | |",
             "|---|---|---|---|---|---|---|---|---|"]
    best = plan.best
    for v in plan.verdicts:
        mark = "pick" if best is not None and v.name == best.name else ""
        lines.append(
            f"| {v.name} | {v.tier_split:g} | {v.rel_area:.3f} | "
            f"{v.rel_pins:.3f} | {v.peak_rho:.2f} | "
            f"{v.access_p99_ns:.0f} | {v.token_p99_ms:.1f} | "
            f"{'ok' if v.meets_slo else 'no'} | {mark} |")
    return "\n".join(lines)


def lut_table() -> str:
    """LUT store economics + grid convergence: cold/warm build rows, the
    grid ladder (interpolation error + fixed-point drift vs grid size),
    and the adaptive refinement trajectory with its <1% final-step
    convergence line -- the LUT-resolution endgame rendered."""
    from benchmarks.lut_convergence import bench_budget, cold_warm, \
        ladder_rows, refine_history
    steps, engine = bench_budget()
    cw = cold_warm()
    lines = [f"Store: cold build {cw['cold_s']:.2f}s vs warm resolution "
             f"{cw['warm_s']:.3f}s ({engine} engine, {steps} ns/cell); "
             f"warm DES traces {cw['warm_traces']}, bit-identical: "
             f"{'yes' if cw['bitident'] else 'NO'}.", "",
             "| grid | cells | interp err (max) | gm drift | "
             "token-p99 drift |",
             "|---|---|---|---|---|"]
    for r in ladder_rows(cw["lut"], steps, engine):
        label = "full" if r["stride"] == 1 else f"stride {r['stride']}"
        lines.append(
            f"| {label} | {r['cells']} | {r['interp_err_max']:.4f} | "
            f"{r['gm_drift_pct']:+.2f}% | {r['tok99_drift_pct']:+.2f}% |")
    hist = refine_history(steps, engine)
    lines += ["", "| refine round | cells | geomean speedup | "
              "token p99 ms | worst probe err | step delta |",
              "|---|---|---|---|---|---|"]
    for r in hist:
        delta = ("" if "d_geomean" not in r else
                 f"gm {100 * r['d_geomean']:.2f}% / "
                 f"p99 {100 * r['d_token_p99']:.2f}%")
        lines.append(
            f"| {r['round']} | {r['cells']} | "
            f"{r['geomean_speedup']:.4f} | {r['token_p99_ms']:.1f} | "
            f"{r['worst_err']:.3f} | {delta} |")
    final = hist[-1]
    if final["converged"]:
        lines += ["", f"Converged: final refinement step moved the "
                  f"geomean speedup {100 * final.get('d_geomean', 0.0):.2f}% "
                  f"and token p99 {100 * final.get('d_token_p99', 0.0):.2f}% "
                  f"(each < 1%)."]
    else:
        lines += ["", "NOT converged within the round budget "
                  f"(last step: gm {100 * final.get('d_geomean', 0.0):.2f}%, "
                  f"p99 {100 * final.get('d_token_p99', 0.0):.2f}%)."]
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--section", default="all",
                    choices=["all", "dryrun", "roofline", "coaxial",
                             "pareto", "drift", "harvest", "serving",
                             "lut"])
    ap.add_argument("--variants", nargs=2, metavar=("ARCH", "SHAPE"),
                    default=None)
    args = ap.parse_args()
    if args.variants:
        print(variant_table(args.variants[0], args.variants[1], args.mesh))
        return
    if args.section in ("all", "dryrun"):
        print(f"### Dry-run ({args.mesh})\n")
        print(dryrun_table(args.mesh))
        print()
    if args.section in ("all", "roofline"):
        print(f"### Roofline ({args.mesh})\n")
        print(roofline_table(args.mesh))
        print()
    if args.section in ("all", "coaxial"):
        print("### Coaxial design-space sweep\n")
        print(coaxial_table())
        print()
    if args.section in ("all", "pareto"):
        print("### Channels x LLC Pareto frontier\n")
        print(pareto_table())
        print()
    if args.section in ("all", "drift"):
        print("### Closed form vs mechanism (headline drift)\n")
        print(drift_table())
        print()
    if args.section in ("all", "harvest"):
        print("### Idle-I/O harvesting frontier\n")
        print(harvest_table())
        print()
    if args.section in ("all", "serving"):
        print("### Serving capacity plan\n")
        print(serving_table())
        print()
    if args.section in ("all", "lut"):
        print("### QueueLUT store & grid convergence\n")
        print(lut_table())
        print()


if __name__ == "__main__":
    main()

"""Read the two ends a limit is set from, and put the control through the
harness's own comparison. By hand, on the chip:

    python3 benchmark/limits.py --workload <name> --seeds 1,2,3 --seconds 30

One deployment, many seeds: for each seed the service makes its weights
anew, a short window at the cell's own load runs (the cell's runner,
unchanged), and the rank compares the sampled requests with the float32
reference: the program's reading (the lower end) and the reading of the int8
control put in the program's place (the upper end). Both go through
``run.judge`` with the cell's limits: the program has to come out correct on
every seed and the control not correct on every seed, or this exits 1.

It also prints statistics that no cell compares, from the per-position
readings, for whoever has to choose a number that separates the two ends.
The benchmark's own runs never do any of this; ``PERF.md`` records what this
printed and the limits set from it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

import run as R


def candidates(pos: dict, margin_min: float, who: str) -> dict:
    """Statistics of one side's per-position readings (``who``: "" for the
    program, "control_" for the control), over decided positions."""
    d = np.asarray(pos["margin"]) >= margin_min
    d = d if d.any() else np.ones_like(d)
    gap, err = np.asarray(pos[who + "gap"]), np.asarray(pos[who + "err"])
    out = {"gap_max_all": gap.max(), "gap_max": gap[d].max(),
           "err_mean_all": err.mean(), "err_mean": err[d].mean(),
           "err_p50": np.quantile(err[d], .5),
           "err_p90": np.quantile(err[d], .9)}
    for q in (.97, .98, .99, .995, .998):
        out[f"gap_p{1000 * q:g}"] = np.quantile(gap[d], q)
    for t in (.1, .2, .3, .5):
        out[f"gap_share_over_{t}"] = (gap[d] > t).mean()
    return {k: float(v) for k, v in out.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 0
    seeds = [int(s) for s in args.seeds.split(",")]
    args.seed = seeds[0]

    def say(msg):
        print(msg, file=sys.stderr, flush=True)

    import kubetorch_tpu as kt
    from kubetorch_tpu.client import shutdown_local_controller
    cell = R.resolve_for(args)
    runner = R.load_file(cell["runner_file"])
    env = R.fabric_env(args.rehearse, cell["cell"]["chips"])
    margin_min = cell["config"].get("router_margin", 0.0)
    rows, rc = [], 0
    try:
        svc, pid = runner.deploy(kt, cell, args, env, say)
        try:
            for i, seed in enumerate(seeds):
                if i:
                    svc.build(seed)
                out = runner.measure(svc, cell, args, seed, say, control=True,
                                     keep_positions=True)
                pos = out["check"].pop("positions")
                rows.append({
                    "seed": seed, "attempted": out["attempted"],
                    "tokens_compared": out["check"]["tokens_compared"],
                    "decided_share": out["check"]["decided_share"],
                    "reference_s": out["check"]["reference_s"],
                    "limits": out["limits"],
                    "program": out["compared"],
                    "control": out["control_compared"],
                    "program_correct": R.judge(out["compared"],
                                               out["limits"]),
                    "control_correct": R.judge(out["control_compared"],
                                               out["limits"]),
                    "program_candidates": candidates(pos, margin_min, ""),
                    "control_candidates": candidates(pos, margin_min,
                                                     "control_")})
                print(json.dumps(rows[-1]), flush=True)
        finally:
            svc.teardown()
            R.wait_pid_gone(pid)
    except BaseException as e:  # noqa: BLE001 — report, clean up
        say(f"FAILED: {type(e).__name__}: {e}")
        R.print_logs()
        rc = 1
    finally:
        shutdown_local_controller()
    if not rows:
        return 1
    summary = {"workload": args.workload, "seeds": len(rows),
               "program_correct_on": sum(r["program_correct"] for r in rows),
               "control_correct_on": sum(r["control_correct"] for r in rows)}
    for side in ("", "_candidates"):
        for k in rows[0]["program" + side]:
            lo = max(r["program" + side][k] for r in rows)
            hi = min(r["control" + side][k] for r in rows)
            summary[k] = {"program_max": lo, "control_min": hi,
                          "ratio": hi / lo if lo > 0 else None}
    print(json.dumps(summary), flush=True)
    out_dir = os.path.join(R.ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"limits_{args.workload}.json"),
              "a") as f:
        f.write(json.dumps({"rows": rows, "summary": summary}) + "\n")
    if summary["program_correct_on"] != len(rows) \
            or summary["control_correct_on"] != 0:
        say(f"the limits do not hold: program correct on "
            f"{summary['program_correct_on']} of {len(rows)} seeds, control "
            f"correct on {summary['control_correct_on']}")
        return 1
    return rc


if __name__ == "__main__":
    sys.exit(main())

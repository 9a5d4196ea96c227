#!/usr/bin/env python3
"""Readings behind a cell's limit on the widest logit gap.

  python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
      [--config <name>] [--traffic <name>] [--seconds <s>] [--rehearse]

In one process (the engine and its programs are set up once): for each
seed, draw that seed's weights into the cell's engine, run the cell's
set-up stream (``warmup.steps`` decode steps, every client started) and a
window of ``--seconds`` (the benchmark's ``run_seconds`` by default),
withdraw the requests still in flight, sample the requests finished in the
window as a benchmark run does, and read two numbers over the sample:

  program  the widest gap of a served token below the float32 reference's
           best logit (the number a run compares with its limit);
  control  the same for the token that the reference computed in int8
           (the precision below the configuration's bfloat16) puts first
           at each of those positions: the reference put in the program's
           place.

Both go through the decision a run makes (``bench.verdict``), so each row
says whether that reading would be ``correct``: the program's must be, the
control's must not.  ``--config`` and ``--traffic`` put another
configuration or mix in the cell's place (to read one that has no cell
yet).  Seeds after the first start with the planner as the previous window
left it.  One JSON line per seed, with the window's throughput, table
widths and gamma histogram, then a summary line.  The benchmark's own runs
do not run the control."""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from contextlib import nullcontext
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
CHECKOUT = CHIP_DIR.parents[1]
sys.path.insert(0, str(CHIP_DIR))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    from harness import bench, models, readers, traffic
    cell, _, _ = bench._cell(CHECKOUT, args.workload)
    conf = models.load_config(args.config or cell["config"])
    mix = traffic.load_traffic(args.traffic or cell["traffic"])
    seconds = args.seconds or float(
        json.loads((CHECKOUT / "BENCHMARK.json").read_text())["run_seconds"])
    import jax
    dev, peaks = bench._device(jax, cell, args.rehearse)
    sys.path.insert(0, str(CHECKOUT / "src"))
    bench._compile_cache(jax, CHECKOUT)
    from repro.kernels import ops
    from harness import driver as drv, system
    from harness.reference import served_gaps

    tspec, dspec = conf["target"], conf["draft"]
    if args.rehearse:
        tspec, dspec = models.rehearsal_spec(tspec), models.rehearsal_spec(dspec)
        mix = traffic.rehearsal_cut(mix)
    limit = conf["logit_gap_limit"]
    rows = []
    with (ops.interpret_kernels() if args.rehearse else nullcontext()):
        engine, tparams, _ = system.build(conf, mix, seeds[0],
                                          rehearse=args.rehearse,
                                          target_spec=tspec, draft_spec=dspec)
        first_id = 0
        for k, seed in enumerate(seeds):
            t0 = time.perf_counter()
            if k:
                del tparams
                gc.collect()
                tparams, _ = system.load_weights(engine, mix, seed, tspec,
                                                 dspec)
            stream = traffic.Stream(mix, seed, tspec["vocab"])
            d = drv.Driver(engine, stream, mix, first_id=first_id)
            with drv.CompileCounter() as counter:
                d.warm_up(counter, prewarm=k == 0)
                win = d.window(seconds, counter)
            ctx = readers.Context(win, tspec, dspec, peaks, 0.0, 0, None)
            steps = ctx.decode_steps()
            cancelled = d.cancel_rest()
            first_id += 1_000_000
            sample = bench._sample(win, seed)
            prog, ctl, n = 0.0, 0.0, 0
            for r in sample:
                g, c = served_gaps(tspec, tparams, r.prompt, r.served,
                                   quant_control=True)
                prog, ctl = max(prog, float(g.max())), max(ctl, float(c.max()))
                n += len(g)
            row = {"seed": seed}
            for who, gap in (("program", prog), ("control", ctl)):
                ok, checks = bench.verdict(
                    {"widest_logit_gap": gap, "tokens": n}, limit,
                    rehearse=args.rehearse)
                row[who] = {"correct": ok, "checks": checks}
            widths: dict = {}
            gammas: dict = {}
            for s in steps:
                widths[s.width] = widths.get(s.width, 0) + 1
                gammas[s.gamma] = gammas.get(s.gamma, 0) + 1
            row.update(
                tokens=n, sequences=len(sample),
                output_tokens_per_s=(None if args.rehearse else
                                     readers.load_reader(
                                         "output_tokens_per_s")(ctx)),
                steps=len(steps), table_width_histogram=widths,
                gamma_histogram=gammas,
                gamma0_step_s_by_width=bench.gamma0_step_seconds(steps),
                lowered_in_window=win.lowerings, cancelled=cancelled,
                seconds=time.perf_counter() - t0)
            rows.append(row)
            print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload, "config": conf["name"],
        "traffic": mix["name"], "device": dev.device_kind, "limit": limit,
        "program_max": max(r["program"]["checks"]["widest_logit_gap"]["value"]
                           for r in rows),
        "control_min": min(r["control"]["checks"]["widest_logit_gap"]["value"]
                           for r in rows),
        "program_correct": all(r["program"]["correct"] for r in rows),
        "control_correct_none": not any(r["control"]["correct"]
                                        for r in rows),
        "seeds": len(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

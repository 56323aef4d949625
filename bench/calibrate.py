#!/usr/bin/env python3
"""The readings the check's limits are set from, for one cell, in one
process on the chip:

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,...
        [--control-seeds a,b,c] [--out results.json]

For every seed of ``--seeds``: the program's first steps through the
window's own call, against the reference (the lower readings). For every
seed of ``--control-seeds``: the reference put in the program's place in
each arithmetic below the one the configuration states (:func:`controls`),
and each fault of ``bench/faults.py`` planted in the program (the upper
readings). A state left unchanged reads 1 by construction and is not run.
Each line printed is one reading; the whole table is written as JSON to
``--out``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def controls(precision):
    """The arithmetics below the one a configuration's ``precision`` entry
    states, each as overrides of ``bench.reference.Arithmetic``: bfloat16
    storage, the dense products' operands in float8 e4m3, and, where the
    body's products run at HIGHEST, the body at HIGH (three bf16 passes)
    and at DEFAULT (one)."""
    out = {"bfloat16_storage": dict(dtype="bfloat16"),
           "float8_products": dict(product_dtype="float8_e4m3fn")}
    if precision["body_products"] == "highest":
        out["high_body"] = dict(body="high")
        out["default_body"] = dict(body="default")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from bench import faults, harness
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(harness.CACHE,
                                                      "autotune.json")
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}; refusing to run", file=sys.stderr)
        return 3
    harness.use_compile_cache(harness.CACHE)
    steps = cell.traffic["check_steps"]
    job0 = harness.prepare(cell, args.seeds[0])
    layout = harness.reference_layout(job0)
    table = {"program": {}, "faults": {}, "controls": {}}

    def note(kind, name, seed, numbers, seconds):
        row = {k: numbers[k] for k in harness.NUMBERS}
        row["worst_leaf"] = numbers["worst_leaf"]
        row["loss_gaps"] = numbers["loss_gaps"]
        row["seconds"] = seconds
        table[kind].setdefault(name, {})[str(seed)] = row
        print(f"{kind} {name} seed={seed} " + " ".join(
            f"{k}={numbers[k]:.6g}" for k in harness.NUMBERS)
            + f" steps={numbers['loss_gaps']} ({seconds:.1f}s)"
            + f" worst={numbers['worst_leaf']}",
            flush=True)

    refs = {}
    for seed in dict.fromkeys(args.seeds + args.control_seeds):
        job = harness.with_seed(job0, seed)
        t0 = time.perf_counter()
        prog = harness.first_steps(job, steps)
        t1 = time.perf_counter()
        refs[seed] = harness.reference_run(job, layout, steps)
        t2 = time.perf_counter()
        note("program", "sound", seed, harness.compare(prog, refs[seed]),
             t1 - t0)
        print(f"  reference {t2 - t1:.1f}s losses {refs[seed].losses} "
              f"program {prog.losses}", flush=True)
    for seed in args.control_seeds:
        job = harness.with_seed(job0, seed)
        for name, kw in controls(cell.config["precision"]).items():
            t0 = time.perf_counter()
            low = harness.reference_run(job, layout, steps, **kw)
            as_prog = harness.Program(low.losses, low.params, low.embeddings,
                                      low.embeddings0)
            note("controls", name, seed,
                 harness.compare(as_prog, refs[seed]),
                 time.perf_counter() - t0)
        for name in faults.for_mode(cell.traffic["mode"]):
            if name == "unchanged_state":
                continue
            t0 = time.perf_counter()
            with faults.FAULTS[name]():
                prog = harness.first_steps(job, steps)
            note("faults", name, seed, harness.compare(prog, refs[seed]),
                 time.perf_counter() - t0)
    table["elapsed_s"] = time.perf_counter() - T_START
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)
    print(json.dumps({"elapsed_s": table["elapsed_s"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The two readings every limit of ``correct`` is set from, on the chip at
the cell's own size, several seeds in one process:

    python -m benchmarks.readings --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1 --fault-seeds 2,3 --faults half_batch

For each seed the program's numbers against the float32 reference (sound
runs: the largest is the limit's lower end), and for each control seed the
reference computed in float8 in the program's place against the same float32
reference (the smallest is the limit's upper end); for each fault seed the
program with the timed path broken underneath (``benchmarks/faults.py``),
which is what a limit the lower precision hardly moves is held against.
Every leaf's norms are written under ``chiprun_out/readings/``.  Training's readings
need no window; serving's need one long enough to finish the mix's longest
requests (``--seconds``).  With ``--whole-runs`` a training cell's seeds are
whole runs instead (set-up, a window of ``--seconds``, reference, every check
line beside its limit, the result line), a minute and the window each: the way
to find which number a seed fails.  The benchmark's own runs never call this.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def train_readings(spec, seed, devices, control, faults, dump_dir):
    """One seed's numbers: the sound program's, each planted fault's and
    (``control``) the float8 reference's, all against one float32
    reference; every leaf's norms go to ``dump_dir`` so that a number can
    be worked out again without the chip."""
    from benchmarks import check, faults as planted, train

    cfg = spec["config"]
    paths, named = train.named_leaves(cfg)
    skip = named.get("leaves_left_out", {})

    def program(wrap=None):
        ready = train.setup(spec, seed, devices, wrap)
        out, pool = ready["program"], ready["pool"]
        train.free(ready.pop("engine"))
        return out, pool

    sound_side, pool = program()
    fault_sides = {name: program(planted.TRAIN_FAULTS[name])[0]
                   for name in faults}
    reference = train.reference_steps(spec, seed, pool, devices)

    def logits(precision):
        return train.reference_eval_logits(
            spec, seed, pool[0], sound_side["eval_rows"],
            sound_side["eval_positions"], devices, precision)

    ref_logits = logits("float32")

    def gaps(side):
        out = {"loss_gap": max(abs(p - r) / abs(r) for p, r in zip(
            side["losses"], reference["losses"]))}
        for key, name in (("grad_norms", "grad_norm"),
                          ("delta_norms", "delta_norm")):
            left = check.left_out(paths, skip.get(key, ()))
            worst, median = check.worst_and_median_gap(
                side[key], reference[key], left)
            each = check.leaf_gaps(side[key], reference[key])
            out[name + "_gap"], out[name + "_median_gap"] = worst, median
            out[name + "_worst"] = [
                [paths[i], round(float(each[i]), 4), bool(left[i])]
                for i in each.argsort()[::-1][:6]]
        if named.get("head_leaves"):
            head = check.left_out(paths, named["head_leaves"])
            for key, name in (("grad_norms", "grad_norm"),
                              ("delta_norms", "delta_norm")):
                out[f"head_{name}_gap"] = check.worst_and_median_gap(
                    side[key], reference[key], ~head)[0]
        if "eval_logits" in side:
            out["eval_logit_gap"] = train.logit_rms_gap(side["eval_logits"],
                                                        ref_logits)
        return out

    sides = {"sound": sound_side, **fault_sides}
    if control:
        sides["control"] = train.reference_steps(spec, seed, pool, devices,
                                                 "fp8")
        sides["control"]["eval_logits"] = logits("fp8")
    row = {"seed": seed, **{name: gaps(side)
                            for name, side in sides.items()}}
    if dump_dir:
        os.makedirs(dump_dir, exist_ok=True)
        sides["reference"] = reference
        dump = {"seed": seed, "workload": spec["name"], "paths": paths}
        for name, side in sides.items():
            dump[name] = {k: [float(x) for x in side[k]]
                          for k in ("losses", "grad_norms", "delta_norms")}
        with open(os.path.join(
                dump_dir, f"{spec['name']}.{seed}.json"), "w") as f:
            json.dump(dump, f)
    return row


def serve_readings(spec, seed, devices, control, seconds):
    from benchmarks import serve

    loop = serve.setup(spec, seed, devices)
    serve.drive(loop, seconds)
    sample = serve.sample_finished(loop.finished, seed)
    serve.free(loop)
    gaps = serve.reference_gaps(spec, seed, sample)
    row = {"seed": seed, "requests": len(sample), "tokens": len(gaps),
           "sound": {"served_logit_gap": float(gaps.max())}}
    if control:
        low = serve.reference_gaps(spec, seed, sample, "fp8")
        row["control"] = {"served_logit_gap": float(low.max())}
        row["control_tokens_moved"] = int((low > 0).sum())
    return row


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    parser.add_argument("--fault-seeds", default="")
    parser.add_argument("--faults", default="half_batch")
    parser.add_argument("--dump-dir", default=os.path.join(
        "chiprun_out", "readings"))
    parser.add_argument("--seconds", type=float, default=25.0)
    parser.add_argument("--whole-runs", action="store_true", help=(
        "training: each seed as a whole run of the cell (set-up, a window "
        "of --seconds, reference, every check line and the result line), "
        "many seeds in one process"))
    args = parser.parse_args(argv)

    from benchmarks import common

    spec = common.load_cell(args.workload)
    devices = common.require_chips(spec["chips"])
    common.configure_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    fault_seeds = {int(s) for s in args.fault_seeds.split(",") if s}
    faults = [f for f in args.faults.split(",") if f]
    if args.whole_runs:
        import time

        from benchmarks import train

        for seed in seeds:
            print(f"whole run seed {seed}", flush=True)
            train.run_cell(spec, seed, args.seconds, 0, time.perf_counter(),
                           devices)
        return 0
    rows = []
    with common.program_log_on_stderr():
        for seed in seeds:
            if spec["config"]["kind"] == "train":
                row = train_readings(
                    spec, seed, devices, seed in controls,
                    faults if seed in fault_seeds else [], args.dump_dir)
            else:
                row = serve_readings(spec, seed, devices, seed in controls,
                                     args.seconds)
            rows.append(row)
            print(json.dumps(row), flush=True)
            if args.dump_dir:     # the tool shows only the end of the output
                os.makedirs(args.dump_dir, exist_ok=True)
                with open(os.path.join(
                        args.dump_dir, args.workload + ".rows.jsonl"),
                        "a") as f:
                    f.write(json.dumps(row) + "\n")
    summary = {}
    for key in rows[0]["sound"]:
        if not key.endswith("_gap"):
            continue
        summary[key] = {"sound_largest": max(r["sound"][key] for r in rows)}
        for side in ("control", *faults):
            summary[key][side + "_smallest"] = min(
                (r[side][key] for r in rows if key in r.get(side, {})),
                default=None)
    print(json.dumps({"workload": args.workload, "summary": summary}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

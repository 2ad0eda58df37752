"""Read, on the chip, the numbers a cell's limits are set from (PERF.md
section 2, "How correct is decided"). One process, one cell:

    python3 benchmark/tests/readings.py --workload r18n8.krum-lie \
        --seeds 12 --control-seeds 3 --first-seed 5000

For each seed the program's first three steps (the compiled step the window
drives, at the cell's own size) against the plain reference: the lower
readings. For the control seeds the reference in fp8 and the planted
half-batch fault against the reference: the upper readings. Every reading
goes through `correct.judge` with the cell's committed limits: ``correct``
and the numbers that ``failed`` it are part of the line (a control or a fault
has to come out not correct). One JSON line per reading on standard output;
``--out`` also appends them to a file.
"""

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--first-seed", type=int, default=5000)
    parser.add_argument("--any-device", action="store_true")
    parser.add_argument("--skip-program", action="store_true",
                        help="read only the control and the fault")
    parser.add_argument("--skip-fault", action="store_true",
                        help="read the control only, not the half batch")
    parser.add_argument("--out")
    args = parser.parse_args(argv)

    import run as run_lib
    from harness import correct, reference, spec, system

    cell = spec.Cell(spec.load(), args.workload)
    run_lib.device_facts(cell.chips, not args.any_device)
    cache_dir = system.enable_compile_cache()
    config, traffic = cell.config, cell.traffic

    def emit(kind, seed, values, where, extra=None):
        ok, check = correct.judge(values, cell.limits)
        failed = [n for n, row in check.items()
                  if not row["value"] <= row["limit"]]
        row = {"workload": cell.name, "kind": kind, "seed": seed,
               "correct": ok, "failed": failed,
               "values": values, "where": where, **(extra or {})}
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as fp:
                fp.write(line + "\n")

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        ref = reference.run(config, traffic, seed)
        if not args.skip_program:
            sut = system.System(config, traffic, seed, cache_dir)
            state, program = run_lib.first_steps(sut)
            sut.free(state)
            values, where = correct.readings(program, ref)
            emit("program", seed, values, where,
                 {"loss": program["loss"], "ref_loss": ref["loss"]})
        if i < args.control_seeds:
            ctl = reference.run(config, traffic, seed, quant="fp8")
            emit("control_fp8", seed, *correct.readings(ctl, ref))
        if i < args.control_seeds and not args.skip_fault:
            half = reference.run(config, traffic, seed,
                                 rows=config["batch_per_worker"] // 2)
            emit("fault_half_batch", seed, *correct.readings(half, ref))


if __name__ == "__main__":
    main()

"""The control of the comparison that decides ``correct``: the reference
computed one precision step below the bfloat16 the configurations state
(float8 e4m3 weights and activations in every linear layer), read at every
position of the same sampled requests as the token it puts first.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 10

runs the cell's timed path for a short window once per seed, in one
process, and prints for each seed one JSON line: the largest reading of
each number for the program (``*.f32``) and for the control (``*.fp8``),
and each one's verdict under the cell's limits file, as ``bench/check.py``
decides ``correct`` (the control's has to come out false). ``--fault
<name>`` plants one of ``bench/faults.py`` under the timed path first. A
limit is set between the largest program reading over a dozen seeds and
the smallest control reading. The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seed: int, seconds: float, *, numerics=("f32", "fp8"),
             require_tpu: bool = True) -> dict:
    from bench import harness

    run = harness.Run(cell, seed, seconds, False, require_tpu=require_tpu)
    run.numerics = tuple(numerics)
    run.execute()
    out = {k: max(v) if v else None for k, v in run.check["readings"].items()}
    out["verdicts"] = run.check["verdicts"]
    out["seed"] = seed
    del run
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", default=None, help="a fault of bench/faults.py to plant")
    ap.add_argument("--numerics", default="f32,fp8", help="f32: the program's readings alone")
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
        p for p in sys.path if os.path.abspath(p or ".") != here]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from bench import harness

    cell = harness.resolve(args.workload, ROOT)
    try:
        harness.require_chips(cell.workload["chips"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if args.fault:
        from bench import faults

        faults.FAULTS[args.fault]()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds,
                                   numerics=args.numerics.split(","))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

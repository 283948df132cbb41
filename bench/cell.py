"""Run one cell of the on-chip benchmark once.

    python3 bench/cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout on a machine with the chips the cell asks for.
``<name>`` is a ``workloads`` entry of ``BENCHMARK.json``; its configuration,
traffic mix, metric readers and limits are found by name under ``bench/``
(see ``bench/harness.py``). ``--trace 0`` prints the cell's end-to-end
metrics; ``--trace 1`` traces part of the window with the JAX profiler and
prints its per-layer metrics. The last line of standard output is the
result as one JSON object; the last lines of standard error give each
number compared with the reference beside its limit. Anything but a TPU,
or fewer chips than the cell asks for, exits non-zero with no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # import ``bench`` as a package and the program from ``src``; the script's
    # own directory comes off the path so its modules cannot shadow others
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
        p for p in sys.path if os.path.abspath(p or ".") != here]
    # the compile cache lives at a fixed path inside the checkout, so that
    # every run of a cell after the first finds its programs there
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from bench import harness

    cell = harness.resolve(args.workload, ROOT)
    try:
        harness.require_chips(cell.workload["chips"])
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    run = harness.Run(cell, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    result = run.execute()
    chk = run.check
    print(f"compiles inside the window: {run.window_compiles}", flush=True)
    late = [s.submitted - s.due for s in run.sent if not s.warm]
    print(f"generator lateness: max {max(late, default=0.0):.6f}s over {len(late)} requests; "
          f"window {run.window_s:.3f}s; setup {run.setup_s:.3f}s", flush=True)
    print(f"reference: {chk['rivers']} rivers, {chk['sides']} sides, {chk['tokens']} served "
          f"tokens compared in {chk['seconds']:.1f}s; readings {json.dumps(chk['readings'])}",
          flush=True)
    for name, v in result["check"].items():
        print(f"check {name} {v['value']} limit {v['limit']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

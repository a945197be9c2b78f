"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the machine this is started on:
one process, no children.  The last line of standard output is the result
as one JSON object.  There is no CPU route: a run that finds no TPU, or
fewer chips than the cell asks for, exits non-zero and prints no result.
"""

import time

_STARTED_AT = time.perf_counter()       # setup_s counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# libtpu otherwise logs under /tmp/tpu_logs, outside the checkout.
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    from benchmark import manifest

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    try:
        cell = manifest.load_cell(args.workload)
    except manifest.ManifestError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2

    # The program's own switch for the persistent cache: it takes
    # JAX_COMPILATION_CACHE_DIR where that is set, else this fixed path
    # inside the checkout.  Every compile is cached, so only a cell's
    # first run in a checkout compiles.
    from horovod_tpu.step_pipeline import enable_compilation_cache

    cache_dir = enable_compilation_cache(
        default=os.path.join(ROOT, ".xla_cache"), min_compile_secs=0.0)

    import jax

    import horovod_tpu as hvd

    hvd.init()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: no TPU (platform {devices[0].platform!r}); "
              "nothing was measured", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"benchmark: {args.workload} needs {cell['chips']} chips, "
              f"JAX sees {len(devices)}; nothing was measured",
              file=sys.stderr)
        return 1
    if not cache_dir:
        print("benchmark: the compilation cache did not engage",
              file=sys.stderr)
        return 1

    from benchmark import harness

    result = harness.run_cell(
        cell, devices, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), started_at=_STARTED_AT,
        trace_dir=os.path.join(ROOT, ".bench_trace", args.workload))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

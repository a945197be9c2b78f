"""python3 benchmark/rehearse.py --workload <cell> [--env KEY=VALUE ...]

Rehearsal 3 of the on-chip-measurement guide: compile the cell's step
program at full size for a *described* TPU v5e (2x2), without a chip, and
print ``memory_analysis()``, the Mosaic calls and the all-reduces.  What
the chip's compiler would refuse (a program that does not fit, a kernel it
cannot tile or partition) is refused here, at no chip time.  Nothing runs:
this prints no time and no metric.

Code of the program that asks JAX for its platform sees the CPU here, so
what ``auto`` would select on the chip has to be pinned by hand: the
seq-4096 cell is rehearsed with ``--env HVDT_FLASH_ATTENTION=on``.
"""

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--env", action="append", default=[])
    args = p.parse_args(argv)
    os.environ.update(kv.split("=", 1) for kv in args.env)

    import importlib

    from jax.experimental import topologies

    from benchmark import harness, manifest, trace_reduce
    from benchmark.layer_metrics.allreduce_bytes import hlo_allreduces

    # Lower Pallas kernels through Mosaic, not the interpreter the CPU
    # platform would select.
    for mod in ("ops.pallas_kernels", "ops.conv_fused", "ops.optim_kernels",
                "quant.kernels"):
        importlib.import_module(
            f"horovod_tpu.{mod}")._use_interpret = lambda: False

    cell = manifest.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    family = manifest.load_family(cell["config_data"]["family"]).build(
        cell["config_data"], cell["traffic"])
    chips = cell["chips"]
    training = harness.Training(family, topo.devices[:chips],
                                cell["traffic"]["per_chip_batch"] * chips)
    memory = training.compiled.memory_analysis()
    hlo = training.hlo_text
    print(memory)
    print(f"peak_hbm_gib by the benchmark's formula: "
          f"{harness.peak_hbm_bytes(memory) / harness.GIB:.3f}")
    print(f"Mosaic custom calls in the step: {hlo.count('tpu_custom_call')}")
    print("instructions that are or fuse a convolution: "
          f"{len(trace_reduce.instructions_holding(hlo, 'convolution'))}")
    found = hlo_allreduces(hlo)
    print(f"all-reduces: {len(found)}, "
          f"{sum(b for b, g in found if g == chips)} bytes over "
          f"{chips} chips")
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result.

    python3 chipbench/run.py --workload olmo-1b.serve.chat --seed 7 \
        --seconds 40 --trace 0

Run from the root of a checkout.  It claims the chips the cell asks for
and refuses to run without them: with no TPU, too few chips, or a device
kind missing from ``chipbench/peaks.json`` it exits non-zero and prints no
result.  The last line of standard output is the result, one JSON object;
the last lines of standard error are the numbers ``correct`` compared,
each with its limit.  With ``--trace 1`` the window is profiled and the
result carries the per-layer metrics instead of the end-to-end ones.
"""
import time

T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import bench  # noqa: E402

T_PROCESS = T_IMPORT - bench.process_age()


class NoChip(Exception):
    pass


def look_for_chips(chips: int, peaks: dict):
    """The first ``chips`` TPU devices, the device as JAX reports it, and
    its row of peaks; raises NoChip otherwise."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {dev.platform} device(s)")
    if dev.device_kind not in peaks:
        raise NoChip(f"device kind {dev.device_kind!r} is not in "
                     f"peaks.json ({sorted(peaks)})")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    return devices[:chips], device, peaks[dev.device_kind]


def use_compile_cache() -> None:
    """The program's compile cache (its fixed directory in the checkout,
    or ``JAX_COMPILATION_CACHE_DIR``), keeping every program, so that only
    a cell's first run in a checkout compiles."""
    import jax
    from repro.launch.cache import use_compile_cache as program_cache
    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def claim(workload: str, root: Path = HERE, look=look_for_chips,
            cache=use_compile_cache):
    """BENCHMARK.json beside the benchmark's directory ``root``, and the
    chips a workload asks for with their device and peaks, the compile
    cache set; raises NoChip without them."""
    spec = json.loads((root.parent / "BENCHMARK.json").read_text())
    cell = bench.find_cell(spec, workload)
    peaks = json.loads((root / "peaks.json").read_text())
    devices, device, peak = look(cell["chips"], peaks)
    cache()
    return spec, devices, device, peak


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, look=look_for_chips, cache=use_compile_cache) -> int:
    args = parse(argv)
    try:
        spec, devices, device, peak = claim(args.workload, HERE, look,
                                            cache)
    except NoChip as e:
        print(f"chipbench: {e}. Nothing was run.", file=sys.stderr)
        return 2
    run = bench.prepare(HERE, spec, args.workload, args.seed, args.seconds,
                        bool(args.trace), device, peak, T_PROCESS, devices)
    out = bench.execute(run)
    bench.emit(out, run.stats["setup_phases"])
    return 0


if __name__ == "__main__":
    sys.exit(main())

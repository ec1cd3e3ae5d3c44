#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run.  It needs the TPU the cell asks for and
has no CPU branch: without one it prints why and exits non-zero with no
result line.  The last line of standard output is the result, and only
a line that ``lastline.validate`` passed is ever printed; everything
else worth reading comes on the lines before it.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)  # the benchmark's own modules
sys.path.insert(1, ROOT)  # the system under test (knn_tpu)


def pin_allocator() -> None:
    """Make glibc's malloc behave alike in every run.  By default its
    mmap threshold adapts to what the process has freed, so a process
    that compiled (a checkout's first run) serves the program's large
    numpy temporaries from a heap the compiler left grown, and one that
    loaded from the cache maps and page-faults them anew in every batch:
    30 ms a batch, 12% of ``sweep_qps`` at ``bigann5m`` (PERF.md, PR 24).
    Pinned: the largest threshold glibc takes (32 MiB), no trimming, so
    both kinds of run reuse the heap after the warm-up."""
    import ctypes

    m_trim_threshold, m_top_pad, m_mmap_threshold = -1, -2, -3
    mallopt = ctypes.CDLL(None).mallopt
    if not (mallopt(m_mmap_threshold, 32 << 20)
            and mallopt(m_trim_threshold, (1 << 31) - 1)
            and mallopt(m_top_pad, 256 << 20)):
        raise OSError("mallopt refused a setting")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        print("run.py: --seed must be >= 0 and --seconds > 0",
              file=sys.stderr)
        return 2

    import harness

    try:
        try:
            pin_allocator()
        except (OSError, AttributeError) as e:
            raise harness.BenchError(
                f"the allocator cannot be pinned (glibc's mallopt): {e}")
        if not os.path.isdir(os.path.join(ROOT, "knn_tpu")):
            raise harness.BenchError(
                f"the system under test (knn_tpu/) is not in {ROOT}: the "
                f"benchmark's own files alone measure nothing")
        import jax

        dev = jax.devices()[0]
        # setup_s counts from here: the interpreter, importing jax and
        # the runtime's reaching the chip are no work of the system's
        # or the benchmark's, and vary by seconds with the machine's
        # state (PERF.md, PR 24)
        t_found = time.perf_counter()
        harness.say(f"jax {jax.__version__}; platform {dev.platform}; "
                    f"device kind {dev.device_kind}; "
                    f"{len(jax.devices())} device(s); start-up (process "
                    f"start to the chip found, not in setup_s): "
                    f"{t_found - T_PROCESS:.3f} s")
        if dev.platform != "tpu":
            raise harness.BenchError(
                f"the benchmark needs a TPU: JAX found platform "
                f"{dev.platform!r} ({dev.device_kind}); it has no CPU "
                f"branch (the CPU tests are benchmark/tests/)")
        import system

        harness.say(f"compile cache: {system.enable_compile_cache()}")
        harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace), t_found)
    except harness.BenchError as e:
        print(f"run.py: no result: {e}", file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

import os
os.environ.setdefault("JAX_ENABLE_X64", "1")   # paper sweeps ε to 1e-9
# One function per paper table. Print ``name,us_per_call,derived`` CSV.
import argparse
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced tolerance sweeps / small graphs")
    ap.add_argument("--only", default=None,
                    choices=[None, "exp1", "exp2", "exp3", "kernels",
                             "roofline", "engines", "trajectory"])
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks.common import header
    from benchmarks import (engine_parity, exp1_error, exp2_matvecs,
                            exp3_runtime, kernel_bench, roofline, trajectory)
    header()
    if args.only in (None, "engines"):
        engine_parity.run(quick=args.quick)
    if args.only in (None, "trajectory"):
        trajectory.run(quick=args.quick)
    if args.only in (None, "exp1"):
        exp1_error.run(quick=args.quick)
    if args.only in (None, "exp2"):
        exp2_matvecs.run(quick=args.quick)
    if args.only in (None, "exp3"):
        exp3_runtime.run(quick=args.quick)
    if args.only in (None, "kernels"):
        kernel_bench.run(quick=args.quick)
    if args.only in (None, "roofline"):
        roofline.run()


if __name__ == '__main__':
    main()

"""Cut a saved trace of one traced solve down to its first loop iteration.

    python3 bench/run.py --workload g500-s21.cold --seed 7 --seconds 30 \
        --trace 1 --save-trace full.json        # on the chip
    python3 bench/tests/trim_trace.py full.json bench/tests/g500_s21_solve.json

Keeps every op and span of the solve but those of loop iterations 2 to the
last, and closes the hole they leave: the time between the end of the first
``psi_power_step`` kernel (found by its op name) and the end of the last one
is taken out of every event, so the ``while`` op and the ``bench.solve``
span that held it end earlier by that much. What is left reads as a solve of
one iteration: its set-up, one gather and one kernel, the loop's last ops,
the epilogue and the copy of ψ to the host.
"""
from __future__ import annotations

import json
import re
import sys

KERNEL = re.compile(r"^%?psi_power_step(?:\.\d+)?\s*=")


def trim(events: dict) -> dict:
    ops = sorted((e for evs in events["devices"].values() for e in evs),
                 key=lambda e: e[1])
    kernels = [e for e in ops if KERNEL.match(e[0])]
    lo = kernels[0][1] + kernels[0][2]
    hi = kernels[-1][1] + kernels[-1][2]

    def at(t: float) -> float:
        return t if t < lo else lo if t < hi else t - (hi - lo)

    def cut(evs: list) -> list:
        out = []
        for e in evs:
            a, b = at(e[1]), at(e[1] + e[2])
            if b > a or (e[2] == 0 and not lo <= e[1] < hi):
                out.append([e[0], a, b - a])
        return out

    return {"devices": {p: cut(evs) for p, evs in events["devices"].items()},
            "spans": cut(events["spans"]),
            "planes": events["planes"],
            "trimmed": {"iterations": len(kernels), "kept": 1,
                        "removed_ns": hi - lo}}


def main(argv: list[str]) -> int:
    src, dst = argv
    with open(src) as f:
        events = json.load(f)
    with open(dst, "w") as f:
        json.dump(trim(events), f, indent=0)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""Turn a capture directory into a kernel ranking + dispatch advice.

Usage: python benchmarks/analyze_capture.py CAPTURE_DIR [...]
       python benchmarks/analyze_capture.py --emit-thresholds CAPTURE_DIR

Reads each directory's ``device_paths.json`` (written by
benchmarks/device_paths.py run on the chip) and
prints, per metric count, the measured ranking plus the winner — then
compares the winners against what ``ops/dispatch.py`` would choose.

``--emit-thresholds`` derives a dispatch threshold table from ONE
capture's winners and writes it to
``loghisto_tpu/ops/dispatch_thresholds.json``, which ``ops/dispatch.py``
loads at import — so refreshing the dispatch policy after a hardware
capture is a committed JSON, not a code edit.
Pure stdlib; safe to run anywhere (no jax import).
"""

from __future__ import annotations

import json
import os
import sys


def _load_dispatch():
    """Load ops/dispatch.py WITHOUT importing the loghisto_tpu package
    (whose __init__ chain pulls in jax) — the module file itself is
    stdlib-only, which keeps this script runnable on any machine holding
    a copy of the capture.  Also the single source of truth for where the
    thresholds file lives (mod.THRESHOLDS_FILE)."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "loghisto_tpu", "ops", "dispatch.py",
    )
    spec = importlib.util.spec_from_file_location("_lh_dispatch", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(dirname: str) -> dict | None:
    path = os.path.join(dirname, "device_paths.json")
    if not os.path.exists(path):
        print(f"{dirname}: no device_paths.json")
        return None
    with open(path) as f:
        return json.load(f)


def analyze(dirname: str, table: dict) -> dict[int, str]:
    rates: dict[str, float] = table.get("rates", {})
    errors: dict[str, str] = table.get("errors", {})
    by_m: dict[int, list[tuple[float, str]]] = {}
    for key, rate in rates.items():
        name, m = key.rsplit("@", 1)
        by_m.setdefault(int(m), []).append((rate, name))
    print(f"\n== {dirname} (platform={table.get('platform')}, "
          f"mode={table.get('mode')}) ==")
    winners: dict[int, str] = {}
    for m in sorted(by_m):
        ranked = sorted(by_m[m], reverse=True)
        winners[m] = ranked[0][1]
        line = " > ".join(f"{n} {r:.3g}" for r, n in ranked)
        print(f"M={m:<6} {line}")
    for key, err in errors.items():
        print(f"   error {key}: {err}")
    if table.get("platform") != "tpu" or not winners:
        return winners
    choose_ingest_path = _load_dispatch().choose_ingest_path

    # captures record their bucket config; older ones predate the field
    num_buckets = table.get("num_buckets", 8193)
    print("dispatch check (auto vs measured winner):")
    for m, winner in sorted(winners.items()):
        auto = choose_ingest_path(m, num_buckets, "tpu")
        # the no-ids pallas row form isn't an (ids, values) candidate;
        # its dispatchable twin is "pallasb"
        mark = "OK" if auto == winner or (
            auto == "pallas" and winner in ("pallas", "pallasb")
        ) else "REVISIT"
        print(f"  M={m:<6} auto={auto:<8} measured={winner:<8} {mark}")
    return winners


SORT_FAMILY = ("sort", "sortscan")


def derive_thresholds(dirname: str, table: dict,
                      winners: dict[int, str]) -> dict | None:
    """One capture's winners -> the dispatch threshold table
    ops/dispatch.py loads.  Policy shape is fixed (pallas at M=1?,
    sort-family above a crossover, scatter between); this derives the
    numbers.  Returns None when the capture can't support the policy
    (not TPU, or no multi-metric rows)."""
    if table.get("platform") != "tpu":
        print(f"{dirname}: not a TPU capture; no thresholds derived")
        return None
    multi = {m: w for m, w in winners.items() if m > 1}
    if not multi:
        print(f"{dirname}: no multi-metric rows; no thresholds derived")
        return None

    sort_wins = sorted(m for m, w in multi.items() if w in SORT_FAMILY)
    other_wins = sorted(m for m, w in multi.items() if w not in SORT_FAMILY)
    if sort_wins and sort_wins[-1] < max(other_wins, default=0):
        # non-monotone table with sort LOSING at the top of the measured
        # range: a threshold would dispatch sort into a region the capture
        # shows another kernel winning — disable instead of extrapolating
        print(f"{dirname}: WARNING sort-family wins at {sort_wins} but "
              f"loses above (others at {other_wins}); disabling the "
              f"sort-family dispatch region")
        sort_wins = []
    if sort_wins:
        lo = max([m for m in other_wins if m < sort_wins[0]] or [1])
        # geometric midpoint of the measured bracket: the crossover is a
        # ratio phenomenon (duplicate density scales with batch/M).
        # Floor of 2 keeps the value inside the loader's smm > 1 guard
        # (M=1 has its own pallas policy axis).
        sort_min = max(2, int(round((lo * sort_wins[0]) ** 0.5)))
        # which sort formulation won at the high-cardinality rows
        kernel = winners[sort_wins[-1]]
    else:
        sort_min = 1 << 30  # sort-family never measured fastest
        kernel = "sort"

    return {
        "source": dirname,
        "platform": "tpu",
        "num_buckets": table.get("num_buckets", 8193),
        "batch": table.get("batch"),
        "mode": table.get("mode"),
        "winners": {str(m): w for m, w in sorted(winners.items())},
        "sort_min_metrics": sort_min,
        "high_cardinality_kernel": kernel,
        "pallas_single_metric": winners.get(1) in ("pallas", "pallasb"),
    }


def main() -> int:
    argv = sys.argv[1:]
    emit = False
    if "--emit-thresholds" in argv:
        emit = True
        argv = [a for a in argv if a != "--emit-thresholds"]
    dirs = argv or sorted(
        d for d in os.listdir(".")
        if d.startswith("TPU_CAPTURE") and os.path.isdir(d)
    )
    if not dirs:
        print("no TPU_CAPTURE* directories here; pass capture dirs as "
              "arguments (e.g. python benchmarks/analyze_capture.py "
              "CAPTURE_DIR)", file=sys.stderr)
        return 1
    if emit and len(dirs) != 1:
        print("--emit-thresholds takes exactly one capture directory "
              "(the table must come from a single hardware ranking)",
              file=sys.stderr)
        return 1
    found = False
    for d in dirs:
        table = load(d)
        if table:
            winners = analyze(d, table)
            found = True
            if emit:
                thresholds = derive_thresholds(d, table, winners)
                if thresholds is None:
                    return 1
                out = _load_dispatch().THRESHOLDS_FILE
                with open(out, "w") as f:
                    json.dump(thresholds, f, indent=1)
                    f.write("\n")
                print(f"\nwrote {out}:")
                print(json.dumps(thresholds, indent=1))
    return 0 if found else 1


if __name__ == "__main__":
    raise SystemExit(main())

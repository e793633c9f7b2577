"""Benchmark entry point: one function per paper table + beyond-paper
comparisons + LM micro-benches.  Prints ``name,us_per_call,derived`` CSV
and optionally machine-readable JSON.

  PYTHONPATH=src python -m benchmarks.run [--full] [--skip-lm] \
      [--skip SECTION ...] [--only SECTION] [--json OUT.json]

Sections: paper, rank_problem, merge, sparse, randomized, streaming,
streaming_scan, streaming_dist, serving, recovery, lm.  ``--only
SECTION`` runs just that section and
``--json OUT.json`` additionally writes one record per row with the
fields CI consumes: ``section``, ``name``, ``shape`` ("MxN" parsed from
the name, null when the row has no shape), ``us_per_call``, ``rel_err``
(the row's relative error / e_sigma when it reports one, else null) and
the raw ``derived`` string.  Every CI benchmark leg gates its JSON with
``scripts/check_bench_json.py`` and uploads it as an artifact.

Each section additionally emits one ``obs_wall_<section>`` record: the
section's wall time, routed through the obs metrics registry
(``bench_section_wall_seconds{section=...}``), plus any compiled peak
bytes the obs drift monitor measured while the section ran.
"""
from __future__ import annotations

import json
import re
import sys

SECTIONS = ("paper", "rank_problem", "merge", "sparse", "randomized",
            "streaming", "streaming_scan", "streaming_dist", "serving",
            "recovery", "lm")

_SHAPE_RE = re.compile(r"(\d+)x(\d+)")
_ERR_RE = re.compile(
    r"(?:rel_err(?:_topk)?|e_sigma|e_vs_dense|max_err)=([0-9.eE+-]+)")


def _record(section: str, name: str, us: float, derived: str) -> dict:
    shape = _SHAPE_RE.search(name)
    err = _ERR_RE.search(derived)
    return {
        "section": section,
        "name": name,
        "shape": shape.group(0) if shape else None,
        "us_per_call": us,
        "rel_err": float(err.group(1)) if err else None,
        "derived": derived,
    }


def _run_paper(rows, full: bool) -> None:
    from benchmarks import paper_tables
    kw = ({"cols": 170_897, "density": 5e-4,
           "blocks": (2, 3, 4, 8, 10, 16, 32, 64, 128)} if full else {})
    for table, method in paper_tables.METHODS.items():
        print(f"# {table} ({method}Checker)", flush=True)
        for r in paper_tables.run_table(method, **kw):
            rows.append((f"{table}_D{r['blocks']}", r["seconds"] * 1e6,
                         f"e_sigma={r['e_sigma']:.3e};e_u={r['e_u']:.3e};"
                         f"lonely={r['lonely_rows']}"))


def _run_rank_problem(rows, full: bool) -> None:
    from benchmarks import rank_problem
    print("# rank problem (paper motivation, emulated undetermined tails)",
          flush=True)
    for r in rank_problem.run():
        rows.append((f"rankproblem_{r['method']}_D{r['blocks']}",
                     r["seconds"] * 1e6,
                     f"e_sigma={r['e_sigma']:.3e};e_u={r['e_u']:.3e};"
                     f"unfixed={r['unfixed_lonely']}"))


def _run_merge(rows, full: bool) -> None:
    from benchmarks import merge_modes
    print("# merge modes (beyond-paper)", flush=True)
    for r in merge_modes.run():
        rows.append((f"merge_{r['merge']}_{r['local']}_D{r['blocks']}",
                     r["seconds"] * 1e6,
                     f"e_sigma={r['e_sigma']:.3e};comm={r['comm_bytes']}"))


def _run_sparse(rows, full: bool) -> None:
    from benchmarks import sparse_path
    print("# sparse vs dense execution path", flush=True)
    for r in sparse_path.run(**({"cols": 170_897} if full else {})):
        rows.append((r["name"], r["seconds"] * 1e6, r["derived"]))


def _run_randomized(rows, full: bool) -> None:
    from benchmarks import randomized
    print("# randomized rank-k sketch vs exact gram (tall-row regime)",
          flush=True)
    for r in randomized.run(**({"ms": (539, 2048, 8192, 32768, 131072)}
                               if full else {})):
        rows.append((r["name"], r["seconds"] * 1e6, r["derived"]))


def _run_streaming(rows, full: bool) -> None:
    from benchmarks import streaming
    print("# streaming svd_update vs from-scratch re-solve", flush=True)
    for r in streaming.run(**({"batch_sizes": (32, 128, 512, 2048)}
                              if full else {})):
        rows.append((r["name"], r["seconds"] * 1e6, r["derived"]))


def _run_streaming_scan(rows, full: bool) -> None:
    from benchmarks import streaming_scan
    print("# one-compilation stream driver (lax.scan windows, rule R6)",
          flush=True)
    for r in streaming_scan.run(**({"window": 32, "batch_rows": 64,
                                    "cols": 2048, "rank": 16}
                                   if full else {})):
        rows.append((r["name"], r["seconds"] * 1e6, r["derived"]))


def _run_streaming_dist(rows, full: bool) -> None:
    from benchmarks import streaming_dist
    print("# distributed streaming ingest (shard_map svd_update, rule R5d)",
          flush=True)
    for r in streaming_dist.run(**({"batch_sizes": (32, 128, 512, 2048)}
                                   if full else {})):
        rows.append((r["name"], r["seconds"] * 1e6, r["derived"]))


def _run_serving(rows, full: bool) -> None:
    from benchmarks import serving
    print("# top-k serving under live ingest (fused kernel, rule R7)",
          flush=True)
    for r in serving.run(**({"universes": (200_000, 1_000_000),
                             "waves": 120} if full else {})):
        rows.append((r["name"], r["seconds"] * 1e6, r["derived"]))


def _run_recovery(rows, full: bool) -> None:
    from benchmarks import recovery
    print("# supervised stream fault recovery (rule R8)", flush=True)
    for r in recovery.run():
        rows.append((r["name"], r["seconds"] * 1e6, r["derived"]))


def _run_lm(rows, full: bool) -> None:
    from benchmarks import lm_step
    print("# lm steps (reduced configs)", flush=True)
    for r in lm_step.run():
        rows.append((f"train_{r['arch']}", r["train_us"], ""))
        rows.append((f"decode_{r['arch']}", r["decode_us"], ""))


_RUNNERS = {
    "paper": _run_paper,
    "rank_problem": _run_rank_problem,
    "merge": _run_merge,
    "sparse": _run_sparse,
    "randomized": _run_randomized,
    "streaming": _run_streaming,
    "streaming_scan": _run_streaming_scan,
    "streaming_dist": _run_streaming_dist,
    "serving": _run_serving,
    "recovery": _run_recovery,
    "lm": _run_lm,
}


def _timed_section(section: str, rows, full: bool):
    """Run one section with its wall time routed through the obs
    metrics registry (``bench_section_wall_seconds{section=...}``) —
    without flipping the global obs gate, so observe-off benchmark
    numbers stay the observe-off numbers.  Returns ``(wall_seconds,
    derived)`` where derived also carries any compiled peak bytes the
    obs drift monitor measured while the section ran (sections that
    exercise observe-on paths populate ``drift_measured_bytes``)."""
    from repro import obs
    from repro.obs import clock

    reg = obs.registry()
    before = set(reg.gauges_with_prefix("drift_measured_bytes"))
    t0 = clock.now()
    _RUNNERS[section](rows, full)
    wall = clock.now() - t0
    reg.gauge_set("bench_section_wall_seconds", wall,
                  labels={"section": section})
    derived = f"wall_s={wall:.3f};source=obs.metrics"
    for k, v in reg.gauges_with_prefix("drift_measured_bytes").items():
        if k in before:
            continue
        # drift_measured_bytes{rule="R7",site="dense"} -> peak_R7_dense_b
        tag = "_".join(re.findall(r'"([^"]+)"', k)) or "measured"
        derived += f";peak_{tag}_b={int(v)}"
    return wall, derived


def main() -> None:
    from repro import compile_cache

    compile_cache.enable()
    argv = sys.argv[1:]
    full = "--full" in argv
    skip = {"lm"} if "--skip-lm" in argv else set()
    # --skip SECTION may repeat: the CI smoke leg skips the sections
    # that already run as dedicated matrix legs.
    for i, a in enumerate(argv):
        if a == "--skip":
            if i + 1 >= len(argv) or argv[i + 1] not in SECTIONS:
                raise SystemExit(
                    f"--skip needs a section; want one of {SECTIONS}")
            skip.add(argv[i + 1])
    only = None
    if "--only" in argv:
        idx = argv.index("--only") + 1
        only = argv[idx] if idx < len(argv) else None
        if only not in SECTIONS:
            raise SystemExit(
                f"--only {only!r}: unknown section; want one of {SECTIONS}")
    json_path = None
    if "--json" in argv:
        idx = argv.index("--json") + 1
        if idx >= len(argv):
            raise SystemExit("--json needs an output path")
        json_path = argv[idx]

    sections = [only] if only else [s for s in SECTIONS if s not in skip]
    records = []
    for section in sections:
        rows = []
        wall, drift = _timed_section(section, rows, full)
        records.extend(_record(section, name, us, derived)
                       for name, us, derived in rows)
        records.append(_record(section, f"obs_wall_{section}",
                               wall * 1e6, drift))

    print("\nname,us_per_call,derived")
    for r in records:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")

    if json_path:
        with open(json_path, "w") as f:
            json.dump(records, f, indent=2)
        print(f"\nwrote {len(records)} records to {json_path}",
              file=sys.stderr)


if __name__ == "__main__":
    main()

"""Goodput/tail regression gate:

    python -m vgate_tpu.loadlab.compare old.jsonl new.jsonl

Exits nonzero when the new artifact regresses against the old one
beyond thresholds, so perf PRs can gate on a recorded baseline:

* per-tier goodput in any matching QPS cell drops more than
  ``--max-goodput-drop`` (absolute fraction, default 0.05),
* TTFT p99 in any matching cell/tier rises more than
  ``--max-tail-rise`` (relative, default 0.25) AND by more than an
  absolute floor (``--tail-floor-ms``, default 50 — sub-floor jitter on
  fast cells is noise, not regression),
* a summary knee moved DOWN a cell: ``max_goodput_qps`` (highest cell
  sustaining goodput >= target) or ``knee_qps`` (peak delivered
  good-QPS).

Cells match on offered QPS; tiers with fewer than ``--min-samples``
requests on either side are skipped (tail statistics on a handful of
requests gate nothing).  ``--cells`` restricts the per-cell gates to
the listed QPS values when only one regime is under test (e.g.
``--cells 14`` gates the overload cell; the summary knee gates are
then skipped — a partial view cannot see a knee move).  Artifacts from different scenarios (name or
content hash) refuse to compare unless ``--allow-cross-scenario``, and
different server-config fingerprints refuse unless
``--allow-config-change`` (the scenario hash cannot see env-exported
server overrides; the fingerprint can).

Exit codes: 0 clean, 1 regression(s), 2 usage/load error.
"""

from __future__ import annotations

import argparse
import sys
from typing import Any, Dict, List, Optional

from .slo import load_artifact


def _cells_by_qps(art: Dict[str, Any]) -> Dict[float, Dict[str, Any]]:
    return {c["qps"]: c for c in art.get("cells", [])}


def _tier_p99(tier_row: Dict[str, Any]) -> Optional[float]:
    return (tier_row.get("ttft_ms") or {}).get("p99")


def compare(
    old: Dict[str, Any],
    new: Dict[str, Any],
    *,
    max_goodput_drop: float = 0.05,
    max_tail_rise: float = 0.25,
    tail_floor_ms: float = 50.0,
    min_samples: int = 8,
    cells: Optional[List[float]] = None,
) -> List[Dict[str, Any]]:
    """Returns the regression list (empty = gate passes).  ``cells``
    restricts the per-cell gates (goodput/tail) to the listed QPS
    values — for gates that target one regime (e.g. the overload
    cell), where a quiet cell's handful of samples would only add
    noise; the summary knee gates are skipped under a filter, since a
    partial view cannot see a knee move."""
    regressions: List[Dict[str, Any]] = []
    old_cells = _cells_by_qps(old)
    new_cells = _cells_by_qps(new)
    gated = set(old_cells) & set(new_cells)
    if cells is not None:
        gated &= set(cells)
    for qps in sorted(gated):
        o_cell, n_cell = old_cells[qps], new_cells[qps]
        if not o_cell.get("valid", True) or not n_cell.get("valid", True):
            continue  # a lag-invalidated cell gates nothing
        o_tiers = o_cell.get("tiers") or {}
        n_tiers = n_cell.get("tiers") or {}
        for tier in sorted(set(o_tiers) & set(n_tiers)):
            o_t, n_t = o_tiers[tier], n_tiers[tier]
            if (
                o_t.get("n", 0) < min_samples
                or n_t.get("n", 0) < min_samples
            ):
                continue
            o_g, n_g = o_t.get("goodput"), n_t.get("goodput")
            if (
                o_g is not None and n_g is not None
                and o_g - n_g > max_goodput_drop
            ):
                regressions.append({
                    "kind": "goodput_drop",
                    "qps": qps,
                    "tier": tier,
                    "old": o_g,
                    "new": n_g,
                    "threshold": max_goodput_drop,
                    "msg": (
                        f"goodput regression: {tier}@{qps:g}qps "
                        f"{o_g:.3f} -> {n_g:.3f} "
                        f"(drop {o_g - n_g:.3f} > {max_goodput_drop})"
                    ),
                })
            o_p99, n_p99 = _tier_p99(o_t), _tier_p99(n_t)
            # the tail gate needs real TTFT samples, not offered
            # requests: a mostly-shed tier can have n=45 offered but a
            # p99 computed over 2 completions — noise, not signal
            o_tn = (o_t.get("ttft_ms") or {}).get("n", 0)
            n_tn = (n_t.get("ttft_ms") or {}).get("n", 0)
            if (
                o_p99 is not None and n_p99 is not None
                and o_tn >= min_samples and n_tn >= min_samples
                and n_p99 - o_p99 > tail_floor_ms
                and o_p99 > 0
                and (n_p99 - o_p99) / o_p99 > max_tail_rise
            ):
                regressions.append({
                    "kind": "tail_rise",
                    "qps": qps,
                    "tier": tier,
                    "old": o_p99,
                    "new": n_p99,
                    "threshold": max_tail_rise,
                    "msg": (
                        f"TTFT p99 regression: {tier}@{qps:g}qps "
                        f"{o_p99:.0f}ms -> {n_p99:.0f}ms "
                        f"(+{(n_p99 - o_p99) / o_p99 * 100:.0f}% > "
                        f"{max_tail_rise * 100:.0f}%)"
                    ),
                })
    o_sum = old.get("summary") or {}
    n_sum = new.get("summary") or {}
    # summary gates are only comparable when both sweeps offered the
    # same cells and no cell was lag-invalidated — a partial or
    # corrupted rerun must not read as a knee move
    summaries_comparable = (
        cells is None
        and o_sum.get("cells") == n_sum.get("cells")
        and not o_sum.get("invalid_cells")
        and not n_sum.get("invalid_cells")
    )
    for key, label in (
        ("max_goodput_qps", "max-goodput-QPS"),
        ("knee_qps", "delivered-goodput knee"),
    ):
        o_knee, n_knee = o_sum.get(key), n_sum.get(key)
        if (
            summaries_comparable
            and o_knee is not None
            and (n_knee is None or n_knee < o_knee)
        ):
            regressions.append({
                "kind": "knee_drop",
                "metric": key,
                "old": o_knee,
                "new": n_knee,
                "msg": (
                    f"{label} moved down: {o_knee:g} -> "
                    f"{n_knee if n_knee is not None else 'none'}"
                ),
            })
    return regressions


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m vgate_tpu.loadlab.compare",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("old", help="baseline artifact (jsonl)")
    parser.add_argument("new", help="candidate artifact (jsonl)")
    parser.add_argument("--max-goodput-drop", type=float, default=0.05)
    parser.add_argument("--max-tail-rise", type=float, default=0.25)
    parser.add_argument("--tail-floor-ms", type=float, default=50.0)
    parser.add_argument("--min-samples", type=int, default=8)
    parser.add_argument(
        "--cells", type=float, nargs="+", default=None,
        help="gate only these QPS cells (e.g. --cells 14 gates the "
             "overload cell of a 2-cell sweep; summary knee gates are "
             "skipped under a filter)",
    )
    parser.add_argument(
        "--allow-cross-scenario", action="store_true",
        help="compare artifacts even when scenario name/hash differ "
             "(implies --allow-config-change)",
    )
    parser.add_argument(
        "--allow-config-change", action="store_true",
        help="compare artifacts whose server config fingerprints "
             "differ (e.g. gating an intentional config-default flip)",
    )
    args = parser.parse_args(argv)
    try:
        old = load_artifact(args.old)
        new = load_artifact(args.new)
    except (OSError, ValueError) as exc:
        print(f"compare: cannot load artifacts: {exc}", file=sys.stderr)
        return 2
    o_meta, n_meta = old["meta"], new["meta"]
    if not args.allow_cross_scenario:
        if o_meta.get("scenario") != n_meta.get("scenario") or (
            o_meta.get("scenario_hash") != n_meta.get("scenario_hash")
        ):
            print(
                "compare: artifacts are from different scenarios "
                f"({o_meta.get('scenario')}/{o_meta.get('scenario_hash')}"
                f" vs {n_meta.get('scenario')}/"
                f"{n_meta.get('scenario_hash')}); pass "
                "--allow-cross-scenario to override",
                file=sys.stderr,
            )
            return 2
    # the scenario hash only covers the YAML; env-exported overrides
    # (a sweep re-points one scenario at 7B / int8 KV) change the
    # SERVER, which the config fingerprint (hashed /stats config block)
    # catches — a different config is a different experiment
    o_fp = o_meta.get("config_fingerprint")
    n_fp = n_meta.get("config_fingerprint")
    if (
        o_fp and n_fp and o_fp != n_fp
        and not args.allow_config_change
        and not args.allow_cross_scenario
    ):
        print(
            "compare: artifacts were measured against differently-"
            f"configured servers (config_fingerprint {o_fp} vs {n_fp});"
            " pass --allow-config-change if the config change is the "
            "thing under test",
            file=sys.stderr,
        )
        return 2
    if o_meta.get("platform") != n_meta.get("platform"):
        print(
            f"compare: WARNING platform changed "
            f"{o_meta.get('platform')} -> {n_meta.get('platform')} — "
            "latency comparisons across platforms are not meaningful",
            file=sys.stderr,
        )
    if args.cells:
        # a filter that matches nothing would silently disable every
        # gate and exit 0 — a typo'd QPS or a scenario whose cells
        # drifted from the recorded baseline must fail loudly, not
        # vacuously pass
        common = {c["qps"] for c in old.get("cells", [])} & {
            c["qps"] for c in new.get("cells", [])
        }
        missing = [q for q in args.cells if q not in common]
        if missing:
            print(
                f"compare: --cells {missing} match no cell present in "
                f"both artifacts (common cells: {sorted(common)})",
                file=sys.stderr,
            )
            return 2
    regressions = compare(
        old, new,
        max_goodput_drop=args.max_goodput_drop,
        max_tail_rise=args.max_tail_rise,
        tail_floor_ms=args.tail_floor_ms,
        min_samples=args.min_samples,
        cells=args.cells,
    )
    if regressions:
        print(f"FAIL: {len(regressions)} regression(s)")
        for r in regressions:
            print(f"  - {r['msg']}")
        return 1
    print(
        f"PASS: no goodput/tail regressions "
        f"({len(old.get('cells', []))} baseline cells vs "
        f"{len(new.get('cells', []))} candidate cells)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Splice generated §Dry-run / §Roofline tables into EXPERIMENTS.md.

Usage: PYTHONPATH=src python -m repro.launch.report
"""
from __future__ import annotations

import json
import pathlib

from repro.configs import registry
from repro.launch import roofline


def dryrun_table(dirpath="results/dryrun") -> str:
    recs = roofline.load(pathlib.Path(dirpath))
    lines = ["| arch | shape | single-pod | compile s | peak GiB/chip "
             "(rolled) | multi-pod (2×16×16) |",
             "|---|---|---|---|---|---|"]
    n_ok_single = n_ok_multi = n_skip = 0
    for arch, shape_name, runs, why in registry.all_cells():
        if not runs:
            n_skip += 1
            lines.append(f"| {arch} | {shape_name} | skipped — "
                         f"{why.split(';')[0].split('—')[0].strip()} | — | — "
                         f"| skipped |")
            continue
        single = recs.get((arch, shape_name, "single_pod", "main"))
        mem = recs.get((arch, shape_name, "single_pod", "mem")) or single
        multi = recs.get((arch, shape_name, "multi_pod", "main"))

        def st(r):
            if r is None:
                return "—"
            return "✓" if r.get("status") == "ok" else r.get("status")

        s_ok = st(single)
        if s_ok != "✓":  # extrapolated cells still count via anchors
            a = roofline.analyse(recs, arch, shape_name, roofline.V5E)
            if a and a.get("status") == "ok":
                s_ok = "✓ (l8 extrapolation)"
        if s_ok.startswith("✓"):
            n_ok_single += 1
        if st(multi) == "✓":
            n_ok_multi += 1
        peak = "?"
        if mem and mem.get("status") == "ok":
            peak = f"{mem['memory']['peak_bytes'] / 2**30:.1f}"
            if mem["memory"]["peak_bytes"] > 16 * 2**30:
                peak += " ⚠"
        comp = single.get("compile_s") if single and single.get(
            "status") == "ok" else None
        lines.append(f"| {arch} | {shape_name} | {s_ok} | "
                     f"{comp if comp else '—'} | {peak} | {st(multi)} |")
    lines.append("")
    lines.append(f"**{n_ok_single} single-pod cells compiled, {n_ok_multi} "
                 f"multi-pod cells compiled, {n_skip} principled skips "
                 f"(= 40 cells accounted).**")
    return "\n".join(lines)


def splice(md_path="EXPERIMENTS.md"):
    p = pathlib.Path(md_path)
    text = p.read_text()
    dr = dryrun_table()
    rf = roofline.table(roofline.V5E)
    text = _replace_block(text, "DRYRUN-TABLE", dr)
    text = _replace_block(text, "ROOFLINE-TABLE", rf)
    p.write_text(text)
    print(f"updated {md_path}")


def _replace_block(text: str, marker: str, content: str) -> str:
    """Replace everything between the marker line and the next section
    heading with the freshly generated content (idempotent)."""
    tag = f"<!-- {marker} -->"
    i = text.index(tag)
    j = text.find("\n## ", i)
    if j == -1:
        j = len(text)
    return text[:i] + tag + "\n\n" + content + "\n" + text[j:]


if __name__ == "__main__":
    splice()

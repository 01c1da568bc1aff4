"""trace_view — convert a DART trace JSONL dump to Chrome trace JSON.

The obs tracer exports its span ring as JSONL
(``obs.get_tracer().export_jsonl(path)``); this tool re-emits it in the
Chrome ``trace_event`` format, loadable in Perfetto
(https://ui.perfetto.dev) or ``chrome://tracing``:

    python tools/trace_view.py spans.jsonl -o spans.trace.json

Phase spans (the dispatcher's ``wait`` / ``select`` / ... /
``resolve``, admission's ``admit``, with their ``sync`` children) land
on one track per thread; request spans on one track per lane
(difficulty class / cascade member / LM shape), so queue waits,
compiled steps and exits line up visually per lane.  Timestamps are on
the wall clock (the dump's ``wall_offset_ns`` header), so the file
lines up with a profiler trace of the same run.  With no ``-o`` the
JSON goes to stdout.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))

from repro.obs.trace import chrome_trace, load_jsonl, load_wall_offset_ns


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("jsonl", help="span dump from Tracer.export_jsonl")
    p.add_argument("-o", "--out", help="output path (default: stdout)")
    args = p.parse_args(argv)
    spans = load_jsonl(args.jsonl)
    doc = chrome_trace(spans, load_wall_offset_ns(args.jsonl))
    text = json.dumps(doc)
    if args.out:
        pathlib.Path(args.out).write_text(text)
        print(f"{len(spans)} spans -> {args.out}", file=sys.stderr)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

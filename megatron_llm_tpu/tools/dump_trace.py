"""Fetch a running server's request trace as a Chrome trace JSON file.

Usage::

    python -m megatron_llm_tpu.tools.dump_trace \
        --url http://127.0.0.1:5000 --out trace.json

Then open ``trace.json`` in ``chrome://tracing`` or https://ui.perfetto.dev.
Each request renders as its own track (``tid`` = request id) with its
``queued`` → ``prefix_match`` / ``prefill`` / ``prefill_chunk[i]`` →
``decode`` → ``retire`` spans; track 0 carries the engine's per-iteration
``engine_step`` spans (batch size and paged/fallback routing in ``args``).
See docs/observability.md.
"""

from __future__ import annotations

import argparse
import json
import sys
from urllib.error import URLError
from urllib.request import urlopen


def fetch_trace(url: str, timeout: float = 10.0) -> dict:
    endpoint = url.rstrip("/") + "/trace"
    with urlopen(endpoint, timeout=timeout) as resp:  # noqa: S310
        return json.loads(resp.read().decode())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--url", default="http://127.0.0.1:5000",
                    help="base URL of a running generation server")
    ap.add_argument("--out", default="trace.json",
                    help="output path for the Chrome trace JSON "
                         "('-' = stdout)")
    ap.add_argument("--timeout", type=float, default=10.0)
    args = ap.parse_args(argv)
    try:
        trace = fetch_trace(args.url, timeout=args.timeout)
    except (URLError, OSError, ValueError) as e:
        print(f"error fetching {args.url}/trace: {e}", file=sys.stderr)
        return 1
    events = trace.get("traceEvents", [])
    dropped = trace.get("otherData", {}).get("dropped_events", 0)
    if args.out == "-":
        json.dump(trace, sys.stdout)
    else:
        with open(args.out, "w") as f:
            json.dump(trace, f)
        print(f"wrote {len(events)} trace events to {args.out}"
              + (f" ({dropped} older events dropped by the ring)"
                 if dropped else ""))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

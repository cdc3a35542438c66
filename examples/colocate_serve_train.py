"""The paper's end-to-end scenario on real models: a high-priority serving
engine (continuous batching) handles bursty traffic while a best-effort
training job consumes idle quanta — Tally's opportunistic policy at work.

    PYTHONPATH=src python examples/colocate_serve_train.py

Add ``--chaos`` to inject a mid-run engine outage (queued requests blow
their per-request timeout) and ``--failover`` to arm the client-side
failover stack — timeout retries with deterministic backoff, hedged
requests, brownout degradation — so the outage degrades latency instead
of losing requests:

    PYTHONPATH=src python examples/colocate_serve_train.py --chaos --failover
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import argparse
import json

import jax

from repro.launch.serve import serve
from repro.obs import ObsHub, prometheus_text


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--chaos", action="store_true",
                    help="inject a mid-run serving outage")
    ap.add_argument("--failover", action="store_true",
                    help="timeout retries + hedging + brownout")
    args = ap.parse_args()
    hub = ObsHub()        # live telemetry: per-request latency histograms
    out = serve("qwen2.5-14b", requests=12, capacity=4,
                max_new_tokens=6, colocate_train=True, obs=hub,
                chaos=args.chaos, failover=args.failover)
    print(json.dumps(out, indent=1))
    print(f"\nserved {out['requests']} requests "
          f"(p99 {out['p99_ms']:.0f} ms, host-side wall time on "
          f"{jax.devices()[0].platform}) while the "
          f"best-effort trainer completed {out['be_quanta']} quanta "
          f"in serving idle gaps")
    if args.chaos:
        print(f"chaos: {out['shed']} requests lost, "
              f"{out['retries']} timeout retries"
              + (" (failover on)" if args.failover else
                 " (failover off — rerun with --failover)"))
    lat = hub.registry.get("tally_serving_request_latency_seconds").child()
    ttft = hub.registry.get("tally_serving_ttft_seconds").child()
    print(f"registry view: {lat.count} requests, "
          f"latency p50≈{lat.quantile(0.5) * 1e3:.0f} ms "
          f"p99≈{lat.quantile(0.99) * 1e3:.0f} ms, "
          f"ttft p99≈{ttft.quantile(0.99) * 1e3:.0f} ms "
          f"(bucketed estimates)")
    text = prometheus_text(hub.registry)
    serving_lines = [ln for ln in text.splitlines()
                     if ln.startswith("tally_serving")
                     and ("_count" in ln or "_total" in ln or "slots" in ln)]
    print("\n".join(serving_lines))


if __name__ == "__main__":
    main()

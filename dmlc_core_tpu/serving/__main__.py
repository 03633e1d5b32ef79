"""Standalone scoring server: ``python -m dmlc_core_tpu.serving``.

The out-of-process entry the chaos suite and chip_smoke.py drive:
compiles the bucket ladder, binds the port, prints one
``SERVE_READY port=<p> pid=<p> platform=<p> devices=<n> device_kind=<k>``
handshake line on stdout, and serves until SIGTERM/SIGINT — which
triggers the draining shutdown (answer every admitted request, shed the
rest, finish every write). SIGKILL is the chaos case: no drain, and the
client must still only ever observe clean errors or complete responses
(every response carries Content-Length, so a torn write never parses as
success).
"""

import argparse
import os
import signal
import sys
import threading

from dmlc_core_tpu.serving.server import ScoringServer, ServingConfig
from dmlc_core_tpu.tpu.runtime import device_report, enable_compile_cache


def main(argv=None) -> int:
    """CLI entry; returns the process exit code."""
    ap = argparse.ArgumentParser(
        description="batched online scoring server (doc/serving.md)")
    ap.add_argument("--model-uri", required=True,
                    help="serving model artifact (save_model checkpoint)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 binds an ephemeral port (printed on stdout)")
    ap.add_argument("--rows-buckets", default="16,64,256,1024",
                    help="comma-separated row-bucket ladder")
    ap.add_argument("--batch-delay-ms", type=float, default=None)
    ap.add_argument("--batch-max-rows", type=int, default=None)
    ap.add_argument("--queue-max", type=int, default=None)
    ap.add_argument("--shed-lateness-ms", type=float, default=None)
    args = ap.parse_args(argv)

    config = ServingConfig(rows_buckets=args.rows_buckets,
                           batch_delay_ms=args.batch_delay_ms,
                           batch_max_rows=args.batch_max_rows,
                           queue_max=args.queue_max,
                           shed_lateness_ms=args.shed_lateness_ms)
    enable_compile_cache()
    server = ScoringServer(model_uri=args.model_uri, host=args.host,
                           port=args.port, config=config)
    server.warm()
    server.start()
    done = threading.Event()

    def _drain(signum, frame):
        done.set()

    signal.signal(signal.SIGTERM, _drain)
    signal.signal(signal.SIGINT, _drain)
    dev = device_report()
    print(f"SERVE_READY port={server.port} pid={os.getpid()} "
          f"platform={dev['platform']} devices={dev['device_count']} "
          f"device_kind={dev['device_kind']}", flush=True)
    done.wait()
    server.stop(drain=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

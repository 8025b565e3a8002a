"""transmogrifai-tpu-serve — serve a saved OpWorkflowModel over HTTP.

Standalone entry (no OpApp subclass needed): point it at a model directory
produced by ``model.save(...)`` / a Train run and it loads, warms every
shape bucket, and serves::

    transmogrifai-tpu-serve /path/to/model --port 8123
    curl -s localhost:8123/score -d '{"x": 1.5, "cat": "a"}'
    curl -s localhost:8123/metrics

Hot-swap a retrained model without dropping requests::

    curl -s localhost:8123/models -d '{"path": "/path/to/model_v2"}'
"""
from __future__ import annotations

import argparse
import sys
from typing import List, Optional


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="transmogrifai-tpu-serve",
        description="Micro-batched online scoring server for a saved model")
    p.add_argument("model", help="saved model directory (model.save output)")
    p.add_argument("--version", default=None, help="version label (default v1)")
    p.add_argument("--tenant", default=None,
                   help="deploy as this named tenant on the shared plane "
                        "(score with ?tenant=NAME; default: the single "
                        "anonymous tenant)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8123)
    p.add_argument("--max-batch", type=int, default=64,
                   help="largest micro-batch / shape bucket")
    p.add_argument("--max-wait-ms", type=float, default=2.0,
                   help="max time a request waits for batchmates")
    p.add_argument("--queue-size", type=int, default=1024,
                   help="admission queue bound (beyond it: HTTP 429)")
    p.add_argument("--replicas", type=int, default=None,
                   help="per-chip model replicas (default: "
                        "TMOG_SERVE_REPLICAS or one per device)")
    p.add_argument("--duration", type=float, default=None,
                   help="seconds to serve (default: until Ctrl-C)")
    args = p.parse_args(argv)

    from ..utils.backend import compile_cache_dir, device_summary

    dev = device_summary()
    print(f"transmogrifai-tpu-serve: platform={dev['platform']} "
          f"kind={dev['kind']} devices={dev['count']} "
          f"compile_cache={compile_cache_dir()}", file=sys.stderr)

    from ..serve import ModelRegistry, ModelServer
    from ..workflow.model import load_model

    registry = ModelRegistry(max_batch=args.max_batch,
                             replicas=args.replicas)
    server = ModelServer(registry, host=args.host, port=args.port,
                         max_batch=args.max_batch,
                         max_wait_ms=args.max_wait_ms,
                         queue_size=args.queue_size)
    print(f"Loading model from {args.model} ...", file=sys.stderr)
    if args.tenant:
        entry = registry.deploy(load_model(args.model), version=args.version,
                                tenant=args.tenant)
    else:
        entry = registry.deploy(load_model(args.model), version=args.version)
    who = f" (tenant {args.tenant})" if args.tenant else ""
    print(f"Deployed {entry.version}{who} (warmed buckets: {entry.buckets}, "
          f"replicas: {len(entry.replicas)})", file=sys.stderr)
    server.start()
    print(f"Serving at {server.url}/score (metrics: {server.url}/metrics)",
          file=sys.stderr)
    try:
        server.wait(args.duration)
    finally:
        server.stop()
        snap = server.metrics.snapshot()
        print(f"Served {snap['responses']} responses "
              f"({snap['shed']} shed, {snap['errors']} errors)",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

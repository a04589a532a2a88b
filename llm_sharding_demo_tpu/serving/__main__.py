"""Process entry point: ``python -m llm_sharding_demo_tpu.serving``.

Replaces the reference's ``uvicorn server:app --host 0.0.0.0 --port 5000``
(reference Dockerfile:19); the port comes from ``SHARD_PORT`` (same env
contract, reference server.py:25) or ``--port``.
"""

from __future__ import annotations

import argparse
import logging

from .app import create_app
from .http import serve
from ..utils import compile_cache
from ..utils.config import from_env


def main() -> None:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    parser = argparse.ArgumentParser()
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=None,
                        help="default: SHARD_PORT env (5000)")
    args = parser.parse_args()
    cfg = from_env()
    logging.getLogger(__name__).info(
        "compile cache at %s", compile_cache.configure())
    app = create_app(cfg)  # create_app joins the multi-host runtime
    port = args.port if args.port is not None else cfg.shard_port
    logging.getLogger(__name__).info(
        "serving role=%s dispatch=%s on %s:%d",
        cfg.shard_role, cfg.dispatch, args.host, port)
    serve(app, host=args.host, port=port)


if __name__ == "__main__":
    main()

"""``python -m k_llms_tpu.serving`` — run the OpenAI-wire front door.

Example::

    python -m k_llms_tpu.serving --backend tpu --model tiny --port 8000 \
        --continuous-batching

    # a 7-8B model on one 16 GB chip needs int8 weights; on a four-chip host
    # the default mesh is all-data (every chip holds the weights), and
    # --model-parallel 2 makes it data=2 x model=2
    python -m k_llms_tpu.serving --backend tpu --model qwen2-7b \
        --quantization int8 --continuous-batching

SIGINT/SIGTERM trigger graceful shutdown: the socket closes, the backend
drains (in-flight decodes finish; late arrivals get typed 503s), then exit.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import logging
import signal

from .app import create_app
from .server import HttpServer


def _parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(prog="python -m k_llms_tpu.serving")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--backend", default="tpu", choices=["tpu", "fake"])
    p.add_argument("--model", default="tiny")
    p.add_argument("--checkpoint-path", default=None)
    p.add_argument("--tokenizer-path", default=None)
    p.add_argument("--max-new-tokens", type=int, default=None)
    p.add_argument(
        "--quantization", default=None, choices=["int8", "int4"],
        help="weight-only quantization (BackendConfig.quantization)",
    )
    p.add_argument(
        "--model-parallel", type=int, default=None,
        help="tensor-parallel degree: the mesh's model axis "
             "(BackendConfig.model_parallel); the rest is data parallel",
    )
    p.add_argument(
        "--continuous-batching", action="store_true",
        help="serve decodes through the in-flight slot loop (streaming-"
             "friendly admission; see engine/continuous.py)",
    )
    p.add_argument("--continuous-width", type=int, default=None)
    p.add_argument(
        "--batch-dir", default=None,
        help="durable root for the offline batch lane's job store "
             "(journal + outputs). Unfinished jobs found here resume at "
             "startup; without it the lane uses an ephemeral tempdir.",
    )
    p.add_argument("--log-level", default="info")
    return p.parse_args(argv)


async def _amain(args: argparse.Namespace) -> None:
    kwargs = {"backend": args.backend, "model": args.model}
    for key in (
        "checkpoint_path", "tokenizer_path", "max_new_tokens", "quantization",
        "model_parallel", "continuous_width",
    ):
        val = getattr(args, key)
        if val is not None:
            kwargs[key] = val
    if args.continuous_batching:
        kwargs["continuous_batching"] = True
    app = create_app(batch_dir=args.batch_dir, **kwargs)
    # Restart recovery before the socket opens: journaled batch jobs resume
    # whether or not the runner speaks the ASGI lifespan protocol.
    await asyncio.to_thread(app.startup)
    server = HttpServer(app, host=args.host, port=args.port)
    await server.start()

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        with contextlib.suppress(NotImplementedError):  # pragma: no cover
            loop.add_signal_handler(sig, stop.set)

    serve_task = asyncio.ensure_future(server.serve_forever())
    await stop.wait()
    serve_task.cancel()
    with contextlib.suppress(asyncio.CancelledError):
        await serve_task
    await server.stop()


def main(argv=None) -> None:
    args = _parse_args(argv)
    logging.basicConfig(
        level=getattr(logging, args.log_level.upper(), logging.INFO),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    asyncio.run(_amain(args))


if __name__ == "__main__":
    main()

"""The benchmark's server child: ``python benchmark/serve.py <config.json> <port> <peak.json> [cpu]``.

``python -m k_llms_tpu.serving`` cannot set ``debug_endpoints`` or
``continuous_max_prompt``, so the benchmark starts the same front door itself:
``create_app(**config["serve"])`` behind ``HttpServer``, the body of
``k_llms_tpu/serving/__main__.py::_amain``. This is the one process that holds
the chip, so on its way out it writes the one device fact ``/healthz`` does
not carry, the allocator's peak on the fullest chip, to ``<peak.json>``. A last
argument ``cpu`` is the rehearsal switch of ``run.py --platform cpu``: the
configuration's model becomes ``tiny`` and ``quantization`` is dropped, nothing
else changes.
"""

import asyncio
import contextlib
import json
import logging
import os
import signal
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from k_llms_tpu.serving.app import create_app  # noqa: E402
from k_llms_tpu.serving.server import HttpServer  # noqa: E402


async def amain(kwargs, port):
    app = create_app(**kwargs)
    await asyncio.to_thread(app.startup)
    server = HttpServer(app, host="127.0.0.1", port=port)
    await server.start()
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    serve_task = asyncio.ensure_future(server.serve_forever())
    await stop.wait()
    serve_task.cancel()
    with contextlib.suppress(asyncio.CancelledError):
        await serve_task
    await server.stop()


def main():
    with open(sys.argv[1]) as f:
        kwargs = dict(json.load(f)["serve"])
    if sys.argv[4:] == ["cpu"]:
        kwargs["model"] = "tiny"
        kwargs.pop("quantization", None)
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    asyncio.run(amain(kwargs, int(sys.argv[2])))
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    with open(sys.argv[3], "w") as f:
        json.dump({"peak_bytes_in_use": max((p for p in peaks if p), default=None)}, f)


if __name__ == "__main__":
    main()

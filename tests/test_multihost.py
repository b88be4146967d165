"""REAL two-process DCN test: jax.distributed over localhost TCP on CPU.

The dryrun and CPU-mesh tests exercise multi-DEVICE sharding inside one
process; this test exercises the multi-HOST path (SURVEY.md §2.3 "DCN for
multi-host fan-out"): two OS processes initialize through
``initialize_multihost``, build one global mesh spanning both, and run a
psum + a sharded matmul whose collectives cross the process boundary. That is
the same wire path a TPU pod's inter-host traffic takes (gRPC/DCN), scaled
down to localhost."""

import os
import socket
import subprocess
import sys

WORKER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.getcwd())

from k_llms_tpu.parallel.distributed import initialize_multihost

ok = initialize_multihost()  # from KLLMS_* env vars
assert ok, "expected distributed initialization"
assert jax.process_count() == 2
assert len(jax.devices()) == 4  # 2 local per process, 4 global

import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

mesh = Mesh(jax.devices(), ("data",))
pid = jax.process_index()

# A data-sharded global array: each process contributes its local shard.
local = jnp.arange(2, dtype=jnp.float32) + 10 * pid  # [10*pid, 10*pid+1]
arrs = jax.make_array_from_single_device_arrays(
    (4,),
    NamedSharding(mesh, P("data")),
    [jax.device_put(local[i : i + 1], d) for i, d in enumerate(jax.local_devices())],
)

@jax.jit
def total(x):
    return jnp.sum(x)  # global reduction -> crosses DCN

t = float(total(arrs))
assert t == 0 + 1 + 10 + 11, t

# A sharded matmul with a psum over the data axis (the coalesced-decode
# collective pattern).
shard_map = jax.shard_map

@jax.jit
def dotsum(x):
    def body(xs):
        return jax.lax.psum(jnp.sum(xs * 2.0), "data")
    return shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P())(x)

d = float(dotsum(arrs))
assert d == 2 * (0 + 1 + 10 + 11), d

# The REAL model across the process boundary: tiny-config forward with the
# batch data-sharded over the 2-process mesh (params replicated), loss
# reduced globally. Identical results on both processes proves the DCN
# collectives carried the cross-host rows.
from k_llms_tpu.models import get_config, init_params
from k_llms_tpu.models.llama import forward

cfg = get_config("tiny").with_(num_layers=2)
params = init_params(cfg, jax.random.key(0))  # same seed -> identical, replicated

import numpy as np

tokens_local = (np.arange(2 * 16, dtype=np.int32).reshape(2, 16) + 100 * pid) % cfg.vocab_size
global_tokens = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("data", None)), tokens_local, (4, 16)
)
mask = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("data", None)), np.ones((2, 16), np.int32), (4, 16)
)

@jax.jit
def loss_fn(params, tokens, mask):
    logits, _ = forward(cfg, params, tokens, mask)
    return jnp.mean(logits.astype(jnp.float32) ** 2)

loss = float(loss_fn(params, global_tokens, mask))
assert loss > 0
print(f"WORKER_{pid}_LOSS={loss:.6f}")
print(f"WORKER_{pid}_OK")
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_workers(port: int):
    procs = []
    try:
        for pid in range(2):
            env = dict(os.environ)
            env.update(
                KLLMS_COORDINATOR=f"127.0.0.1:{port}",
                KLLMS_NUM_PROCESSES="2",
                KLLMS_PROCESS_ID=str(pid),
                JAX_PLATFORMS="cpu",
            )
            # A fresh interpreter per process: jax.distributed must initialize
            # before any backend use, which pytest's own process already did.
            procs.append(
                subprocess.Popen(
                    [sys.executable, "-c", WORKER],
                    env=env,
                    cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    text=True,
                )
            )
        outs = []
        for p in procs:
            try:
                outs.append(p.communicate(timeout=150)[0])
            except subprocess.TimeoutExpired:
                # A lost coordinator-port race can leave a worker blocked on
                # connect rather than exiting; kill it, keep whatever it
                # printed, and surface the round as failed so the caller's
                # fresh-port retry applies to this mode too. Per-process
                # communicate keeps the healthy worker's output intact.
                p.kill()
                outs.append(p.communicate()[0] or "")
        return outs, procs
    finally:
        for p in procs:  # a hung coordinator must not leak past the test
            if p.poll() is None:
                p.kill()
                p.communicate()


def test_two_process_dcn_collectives():
    # _free_port has an unavoidable close-to-rebind window; retry once with a
    # fresh port if the coordinator lost the race (clean bind failure or a
    # worker left hanging on the stolen port — both count as a lost round).
    for attempt in range(2):
        outputs, procs = _run_workers(_free_port())
        if all(p.returncode == 0 for p in procs) or attempt == 1:
            break
    for pid, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out}"
        assert f"WORKER_{pid}_OK" in out
    # The globally-reduced model loss must be identical on both processes.
    losses = [
        line.split("=")[1]
        for out in outputs
        for line in out.splitlines()
        if "_LOSS=" in line
    ]
    assert len(losses) == 2 and losses[0] == losses[1], losses

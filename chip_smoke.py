#!/usr/bin/env python3
"""Run the NoM fabric and the serving Engine once on a TPU, and check them.

    python chip_smoke.py               # one chip: fabric phase, serve phase
    python chip_smoke.py --four-chips  # four chips: collectives and MoE
                                       # expert dispatch, nothing else

Every phase runs in this one process, through the entry points a user
calls, and raises on a failed check, so any failure exits non-zero.  A
host whose first JAX device is not a TPU exits non-zero before any phase
and names the platform it found: nothing falls back to the CPU.  Progress
goes to earlier lines; the last line of stdout is one JSON object naming
the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import (NomFabric, TransferRequest,  # noqa: E402
                        make_topology, nom_all_to_all, nom_allreduce)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh, set_ambient_mesh  # noqa: E402
from repro.launch.serve import serve  # noqa: E402
from repro.models.moe import MoE, MoEConfig  # noqa: E402

# Fabric phase: one batch of 4 KiB page copies and in-place INIT scrubs on
# the paper's 8x8x4 bank mesh with 16 TDM slots.
FABRIC_REQUESTS = 256
FABRIC_INIT_SHARE = 8          # one request in 8 is an INIT scrub
PAGE_BYTES = 4096
N_SLOTS = 16

# Serve phase: qwen1.5-4b at its published widths, depth cut to 16 of its
# 40 layers (about 8.2 GB of fp32 weights on a 16 GB chip).
SERVE_ARCH = "qwen1.5-4b"
SERVE_LAYERS = 16
SERVE_REQUESTS = 3
SERVE_BATCH = 4
PROMPT_LEN = 32
NEW_TOKENS = 16
# Teacher-forced decode through the KV cache against the full forward, as
# the largest |difference| of the log-softmax over the vocabulary.  Both
# programs compute in bfloat16 (8-bit mantissa) from the same fp32
# weights, but XLA fuses and orders the reductions of a one-token step
# differently from those of the whole sequence, so the two round apart
# by a few bf16 ulps per layer.  A cache fault (a wrong position, a stale
# or missing key) moves the log-probabilities by whole nats.  Greedy
# agreement is printed, not checked: random weights leave near-ties among
# the top logits that a bf16 ulp can flip.
LOGPROB_TOL = 0.25

# Four-chip phase: phi3.5-moe's published expert widths, one MoE layer.
MOE_ARCH = "phi3.5-moe-42b-a6.6b"
EP = 4


def log(msg: str) -> None:
    print(msg, flush=True)


def tpu_devices(n: int) -> list:
    """The first ``n`` devices, or exit non-zero unless they are TPUs."""
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{platform!r}")
    if len(devices) < n:
        sys.exit(f"chip_smoke: needs {n} TPU devices, found "
                 f"{len(devices)}")
    return devices[:n]


# ---------------------------------------------------------------------------
# Fabric phase
# ---------------------------------------------------------------------------
def fabric_requests(mesh, n: int) -> list[TransferRequest]:
    """``n`` seeded page transfers between random distinct banks, one in
    ``FABRIC_INIT_SHARE`` of them an in-place INIT scrub instead."""
    rng = np.random.default_rng(0)
    src = rng.integers(mesh.n_nodes, size=n)
    dst = (src + 1 + rng.integers(mesh.n_nodes - 1, size=n)) % mesh.n_nodes
    init = rng.permutation(n) < n // FABRIC_INIT_SHARE
    return [TransferRequest(int(s), int(s), PAGE_BYTES, op="init") if i
            else TransferRequest(int(s), int(d), PAGE_BYTES)
            for s, d, i in zip(src, dst, init)]


def circuit_bytes(results) -> bytes:
    """Canonical bytes of a batch's granted circuits (None = denied)."""
    rows = []
    for r in results:
        c = r.circuit
        rows.append(None if c is None else [
            int(c.src), int(c.dst), int(c.start_cycle), int(c.n_windows),
            int(c.slots_per_window), bool(c.uses_bus), int(c.bus_column),
            int(c.distance), [int(v) for hop in c.hops for v in hop]])
    return json.dumps(rows).encode()


def fabric_phase() -> dict:
    """Schedule one batch on the default (``auto``) fabric, then the same
    batch on a host-pipeline fabric; both must grant byte-identical
    circuits, and the default fabric must have run the compiled prepare
    program at least once."""
    mesh = make_topology()
    reqs = fabric_requests(mesh, FABRIC_REQUESTS)
    runs = {}
    for name, fabric in (
            ("auto", NomFabric(mesh=mesh, n_slots=N_SLOTS)),
            ("host", NomFabric(mesh=mesh, n_slots=N_SLOTS,
                               alloc_backend="host"))):
        results, _ = fabric.schedule(reqs)
        tel = fabric.telemetry()
        runs[name] = (circuit_bytes(results), tel)
        log(f"fabric[{name}]: {tel['scheduled']}/{tel['requests']} "
            f"scheduled ({tel['init_requests']} INIT), fused_waves="
            f"{tel['fused_waves']} host_waves={tel['host_waves']} "
            f"conflicts={tel['conflicts']} max_inflight="
            f"{tel['max_inflight']} stall_cycles={tel['stall_cycles']}")
    tel = runs["auto"][1]
    if tel["fused_waves"] < 1:
        raise AssertionError("the compiled prepare program served no wave "
                             "(fused_waves=0)")
    if tel["scheduled"] < 1:
        raise AssertionError("the fabric granted no circuit")
    if runs["auto"][0] != runs["host"][0]:
        raise AssertionError("fabric[auto] circuits differ from the host "
                             "pipeline's")
    log(f"fabric: auto circuits byte-identical to host "
        f"({len(runs['host'][0])} bytes)")
    return tel


# ---------------------------------------------------------------------------
# Serve phase
# ---------------------------------------------------------------------------
def teacher_forced_error(model, params, tokens) -> tuple[float, float]:
    """Decode ``tokens`` one step at a time through the cache and compare
    with one full forward: (max |log-softmax difference|, share of
    positions whose argmax agrees)."""
    b, s = tokens.shape
    step = jax.jit(model.decode_step)
    caches = model.init_caches(b, s)
    outs = []
    for i in range(s):
        logits, caches = step(params, tokens[:, i:i + 1], caches,
                              jnp.int32(i))
        outs.append(logits)
    dec = jnp.concatenate(outs, axis=1).astype(jnp.float32)
    full = jax.jit(lambda p, t: model.apply(p, t, remat=False)[0])(
        params, tokens).astype(jnp.float32)
    for name, x in (("decode", dec), ("forward", full)):
        if not bool(jnp.isfinite(x).all()):
            raise AssertionError(f"{name} logits are not finite")
    a = jax.nn.log_softmax(full, axis=-1)
    c = jax.nn.log_softmax(dec, axis=-1)
    err = float(jnp.max(jnp.abs(a - c)))
    agree = float((a.argmax(-1) == c.argmax(-1)).mean())
    return err, agree


def serve_phase(device, n_layers: int | None = SERVE_LAYERS, *,
                n_requests: int = SERVE_REQUESTS, batch: int = SERVE_BATCH,
                prompt_len: int = PROMPT_LEN, new_tokens: int = NEW_TOKENS,
                smoke: bool = False) -> dict:
    """Serve ``n_requests`` requests through ``repro.launch.serve`` with
    the fabric tracking every cache transfer, then check the tokens, the
    fabric's work, and teacher-forced decode against the full forward."""
    full_depth = get_config(SERVE_ARCH, smoke=smoke).n_layers
    run = serve(SERVE_ARCH, n_layers=n_layers, batch=batch,
                prompt_len=prompt_len, new_tokens=new_tokens,
                n_requests=n_requests, smoke=smoke)
    cfg = run.cfg
    stats = device.memory_stats() or {}
    log(f"serve: {cfg.name} at published widths (d_model={cfg.d_model}, "
        f"{cfg.n_heads}x{cfg.head_dim} heads, d_ff={cfg.d_ff}, "
        f"vocab={cfg.vocab}, qkv_bias={cfg.qkv_bias}); depth cut to "
        f"{cfg.n_layers} of {full_depth} layers; peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    want = (batch, prompt_len + new_tokens)
    for r, toks in enumerate(run.tokens):
        if toks.shape != want:
            raise AssertionError(f"request {r}: tokens {toks.shape}, "
                                 f"want {want}")
        t = np.asarray(toks)
        if t.min() < 0 or t.max() >= cfg.vocab:
            raise AssertionError(f"request {r}: token ids outside vocab")
    tel = run.telemetry
    log(f"serve: fabric steps={tel.get('steps')} requests="
        f"{tel.get('requests')} scheduled={tel.get('scheduled')} "
        f"init_requests={tel.get('init_requests')} peak_tenants="
        f"{tel.get('peak_tenants')} active_tenants="
        f"{tel.get('active_tenants')} shed_tenants={tel.get('shed_tenants')}"
        f" stall_cycles={tel.get('stall_cycles')}")
    if not tel or tel["scheduled"] < 1:
        raise AssertionError("the fabric scheduled no cache transfer")
    if tel["shed_tenants"] or tel["active_tenants"]:
        raise AssertionError("a request was shed or never torn down")
    if tel["init_requests"] < n_requests:
        raise AssertionError("teardown scheduled fewer INIT scrubs than "
                             "there were tenants")
    err, agree = teacher_forced_error(run.model, run.params, run.tokens[0])
    log(f"serve: teacher-forced decode vs forward: max |dlogp|={err!r} "
        f"(tol {LOGPROB_TOL}), greedy agreement={agree!r}")
    if not err <= LOGPROB_TOL:
        raise AssertionError("teacher-forced decode diverges from the "
                             "full forward")
    return tel


# ---------------------------------------------------------------------------
# Four-chip phase
# ---------------------------------------------------------------------------
def _sharded(fn, mesh, spec):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False))


def _devices_of(x) -> set:
    return {s.device for s in x.addressable_shards}


def four_chip_phase(devices, *, moe_cfg: MoEConfig | None = None,
                    a2a_rows: int = 512, width: int = 4096,
                    moe_tokens: tuple[int, int] = (2, 512)) -> None:
    """Ring collectives against XLA's and one expert-parallel MoE layer
    with ``dispatch="nom"`` against ``"xla"``, on a (1, N) mesh."""
    n = len(devices)
    mesh = set_ambient_mesh(make_mesh((1, n), ("data", "model"),
                                      devices=devices))
    k_a2a, k_ar, k_x, k_p = jax.random.split(jax.random.PRNGKey(0), 4)
    spec = P("model")

    x = jax.device_put(
        jax.random.normal(k_a2a, (n * n, a2a_rows, width), jnp.float32),
        NamedSharding(mesh, spec))
    nom = _sharded(lambda v: nom_all_to_all(v, "model"), mesh, spec)(x)
    ref = _sharded(lambda v: jax.lax.all_to_all(v, "model", 0, 0),
                   mesh, spec)(x)
    if len(_devices_of(nom)) != n:
        raise AssertionError("nom_all_to_all output is not on every device")
    if not bool(jnp.array_equal(nom, ref)):
        raise AssertionError("nom_all_to_all differs from lax.all_to_all")
    log(f"four-chip: nom_all_to_all == lax.all_to_all on {x.shape} "
        f"{x.dtype}, shards on {len(_devices_of(nom))} devices")

    v = jax.device_put(
        jax.random.normal(k_ar, (n, a2a_rows * width), jnp.float32),
        NamedSharding(mesh, spec))
    nom = _sharded(lambda u: nom_allreduce(u[0], "model")[None], mesh,
                   spec)(v)
    ref = _sharded(lambda u: jax.lax.psum(u, "model"), mesh, spec)(v)
    # The ring adds the N operands in a fixed order of its own, psum in
    # XLA's: fp32 sums of N unit normals agree to a few ulps of their size.
    diff = float(jnp.max(jnp.abs(nom - ref)))
    if len(_devices_of(nom)) != n or diff > 1e-5 * n:
        raise AssertionError(f"nom_allreduce differs from psum by {diff!r}")
    log(f"four-chip: nom_allreduce vs psum on {v.shape} {v.dtype}: "
        f"max |diff|={diff!r}, shards on {len(_devices_of(nom))} devices")

    if moe_cfg is None:
        arch = get_config(MOE_ARCH)
        moe_cfg = MoEConfig(arch.d_model, arch.moe_dff, arch.n_experts,
                            arch.top_k, norm_topk=arch.norm_topk)
    moe = MoE(moe_cfg)
    if moe._ep_size() != n:
        raise AssertionError(f"MoE sees EP size {moe._ep_size()}, want {n}")
    shardings = {k: NamedSharding(mesh, s)
                 for k, s in moe._param_specs().items()}
    params = jax.jit(moe.init, out_shardings=shardings)(k_p)
    tokens = jax.random.normal(k_x, (*moe_tokens, moe_cfg.d_model),
                               jnp.float32)
    outs = {}
    for dispatch in ("nom", "xla"):
        layer = MoE(dataclasses.replace(moe_cfg, dispatch=dispatch))
        y, aux = layer.apply(params, tokens)
        if len(_devices_of(y)) != n:
            raise AssertionError(f"MoE[{dispatch}] output is not on every "
                                 "device")
        if not bool(jnp.isfinite(y).all()):
            raise AssertionError(f"MoE[{dispatch}] output is not finite")
        outs[dispatch] = np.asarray(y)
        rep = layer.last_dispatch_report
        log(f"four-chip: MoE[{dispatch}] d_model={moe_cfg.d_model} "
            f"d_ff={moe_cfg.d_ff} experts={moe_cfg.n_experts} "
            f"top_k={moe_cfg.top_k} EP={layer._ep_size()} "
            f"y{y.shape} on {len(_devices_of(y))} devices, aux="
            f"{float(aux)!r}" + (
                "" if rep is None else
                f", dispatch plan {rep.n_scheduled}/{rep.n_requests} "
                f"blocks in {rep.n_windows} rounds"))
    # Both dispatches hand identical token blocks to identical expert
    # GEMMs; only the exchange between them differs.
    scale = float(np.abs(outs["xla"]).max())
    diff = float(np.abs(outs["nom"] - outs["xla"]).max())
    if diff > 1e-4 * scale:
        raise AssertionError(f"MoE nom differs from xla by {diff!r} "
                             f"(max |y|={scale!r})")
    log(f"four-chip: MoE nom vs xla max |diff|={diff!r} "
        f"(max |y|={scale!r})")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase")
    args = ap.parse_args(argv)
    devices = tpu_devices(EP if args.four_chips else 1)
    log(f"cache: {enable_compile_cache()}")
    if args.four_chips:
        four_chip_phase(devices)
    else:
        with jax.default_device(devices[0]):
            fabric_phase()
            serve_phase(devices[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()

"""Serve internvl2-2b at full width on a TPU v5e chip, through the fleet.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # mesh-sharded replica, four chips

The default run builds internvl2-2b at its published widths and full
depth (24 layers, d_model 2048), with random weights made on the device
from ``--seed``.  It serves 16 seeded requests through ``FleetEngine``
(one replica) and ``FleetFrontend``: the path that
``python -m repro.launch.serve --engine fleet`` takes.  Prompts have 256 to
2048 tokens and ask for 32 to 128 new ones.  The pool is 8 slots of up
to 4096 tokens in pages of 128 tokens, 257 pages in all.  Prompts are
text only: the model's vision front end is a stub, and it is not used.

The checks are:

* every request finishes with exactly the tokens it asked for;
* the fleet's invariants hold, and no page leaks;
* every logit row the sampler sees is finite;
* the last-position logits of one prompt's paged prefill agree with
  ``T.forward`` on the same prompt (``LOGIT_TOL`` says how closely, and
  why).

``--four-chips`` runs only the mesh-sharded replica (``make_serve_mesh(4)``
with ``MESH_SERVE_RULES``, the ``--mesh-shape 4`` path) and the unsharded
engine it is compared with, in this process and on the same requests. It
checks that the pool's shards sit on four distinct devices, and that the
token streams are bit-identical (DESIGN.md section 11).

The script needs a TPU whose ``device_kind`` has a published spec. With
no such chip, or outside the repository, it exits non-zero and prints no
result line. Otherwise the last line of its output is one JSON object
naming the device. The wall rate it prints comes from a smoke run and is
not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import jaxcache  # noqa: E402

jaxcache.enable_env()       # before jax is imported: it reads JAX_* then

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.core.devices import tpu_spec_for_kind  # noqa: E402
from repro.launch.mesh import make_serve_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serve.fleet import FleetEngine  # noqa: E402
from repro.serve.frontend import FleetFrontend  # noqa: E402

ARCH = "internvl2-2b"
REQUESTS = 16
PROMPT_LEN = (256, 2048)
NEW_TOKENS = (32, 128)
SLOTS, MAX_LEN, PAGE_LEN = 8, 4096, 128
NUM_PAGES = SLOTS * MAX_LEN // PAGE_LEN + 1      # + the scratch page

#: bound on max |paged - forward| over the last-position logits, as a share
#: of the forward's largest |logit|.  Both paths run the same bf16 weights
#: and activations, but round at different points: the paged prefill works
#: in chunks of 128 tokens (other matmul shapes, so other accumulation
#: orders) and reads K/V back from the bf16 pool, and the residual stream
#: carries those roundings through 24 layers.  On the CPU, at this depth
#: with narrower layers, the two agree to 0.019; a position off by one or
#: a mask one token too wide moves them by about half (0.48 and 0.56).
#: The bound sits between, with room on both sides.
LOGIT_TOL = 0.1

_compile_s: dict[str, float] = defaultdict(float)


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        name = str(kw.get("fun_name", "?"))     # "jit(decode_fn)"
        _compile_s[name.removeprefix("jit(").removesuffix(")")] += duration


def check(ok: bool, what: str) -> None:
    """A failed check ends the run (and is kept under ``python -O``)."""
    if not ok:
        raise AssertionError(what)


def require_tpu(count: int):
    """The first ``count`` devices and their spec, or SystemExit."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's default backend is "
                         f"{devices[0].platform!r}; this run needs the chip")
    if len(devices) < count:
        raise SystemExit(f"need {count} TPU devices, found {len(devices)}")
    kind = devices[0].device_kind
    spec = tpu_spec_for_kind(kind)          # unknown kind -> ValueError
    print(f"device: {kind} x{len(devices)} (platform tpu), priced as "
          f"{spec.name}: {spec.peak_bf16_flops:g} FLOP/s bf16, "
          f"{spec.hbm_bytes_per_s:g} B/s HBM")
    return devices[:count], spec


def make_requests(cfg, seed: int, n: int = REQUESTS,
                  prompt_len=PROMPT_LEN, new_tokens=NEW_TOKENS):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        plen = int(rng.integers(prompt_len[0], prompt_len[1] + 1))
        n_new = int(rng.integers(new_tokens[0], new_tokens[1] + 1))
        out.append((rng.integers(cfg.vocab_size, size=plen)
                    .astype(np.int32), n_new))
    return out


class LogitsProbe:
    """Greedy sampler that also watches the logits it samples from.

    ``finite`` (a device scalar, so watching costs no host sync) stays
    True while every row handed over is finite.  ``first_prefill`` keeps
    the first 1-D row: the last prompt position of the first request to
    finish its prefill.  With one replica that is uid 0, since the engine
    prefills in admission order."""

    def __init__(self):
        self.finite = jnp.array(True)
        self.first_prefill = None

    def __call__(self, logits):
        self.finite = self.finite & jnp.isfinite(logits).all()
        if logits.ndim == 1 and self.first_prefill is None:
            self.first_prefill = logits
        return jnp.argmax(logits, -1)


def serve(cfg, params, requests, spec, *, mesh=None, slots=SLOTS,
          max_len=MAX_LEN, page_len=PAGE_LEN, num_pages=NUM_PAGES):
    """Serve ``requests`` through one fleet replica; check and return
    (token streams, probe, fleet, wall seconds)."""
    probe = LogitsProbe()
    fleet = FleetEngine(cfg, params, max_slots=slots, max_len=max_len,
                        replicas=1, profiles=[spec], page_len=page_len,
                        num_pages=num_pages, sampler=probe, mesh=mesh)
    front = FleetFrontend(fleet)
    t0 = time.perf_counter()
    for uid, (prompt, n_new) in enumerate(requests):
        front.submit_blocking(prompt, n_new, uid=uid)
    handles = front.run()
    wall = time.perf_counter() - t0

    check(len(handles) == len(requests),
          f"{len(handles)} streams for {len(requests)} requests")
    for h, (_, n_new) in zip(handles, requests):
        check(h.done and len(h.tokens) == n_new,
              f"uid {h.uid}: done={h.done}, {len(h.tokens)}/{n_new} tokens")
    fleet.check_invariants()
    stats = fleet.stats()
    check(stats["pages_leaked"] == 0, f"{stats['pages_leaked']} pages leaked")
    check(bool(probe.finite), "non-finite logits")
    tokens = sum(len(h.tokens) for h in handles)
    label = "one-chip smoke" if mesh is None else "mesh smoke"
    print(f"served {stats['finished']}/{len(requests)} requests, {tokens} "
          f"tokens in {stats['ticks']} fleet ticks, {wall:.3f} s wall "
          f"({tokens / wall:.1f} tok/s; {label}, not a benchmark)")
    print(f"pages: peak={stats['peak_pages']} of {num_pages}, leaked="
          f"{stats['pages_leaked']}, preemptions={stats['preemptions']}; "
          f"invariants hold; all logits finite")
    return [h.tokens for h in handles], probe, fleet, wall


def reference_error(cfg, params, prompt, row) -> float:
    """max |row - forward's last-position logits| / max |forward's|."""

    def forward_last(p, tokens):
        return T.forward(p, cfg, {"tokens": tokens})[0][0, -1]

    ref = jax.jit(forward_last)(params, jnp.asarray(prompt[None]))
    return float(jnp.abs(row - ref).max() / jnp.abs(ref).max())


#: jitted programs by function name: weights, prefill chunk, decode step,
#: and the reference forward
_STEPS = ("init_params", "chunk_fn", "decode_fn", "forward_last")


def _print_compiles(label: str) -> None:
    rest = sum(v for k, v in _compile_s.items() if k not in _STEPS)
    print(f"compile seconds ({label}): "
          + ", ".join(f"{k}={_compile_s[k]:.2f}" for k in _STEPS
                      if k in _compile_s)
          + f", other={rest:.2f}")
    _compile_s.clear()


def _peak_bytes(devices) -> str:
    return ", ".join(
        f"{d.id}:{d.memory_stats()['peak_bytes_in_use'] / 2**30:.2f} GiB"
        for d in devices)


def build(seed: int):
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    cfg = configs.get_config(ARCH)

    def init_params(key):
        return T.init_params(cfg, key)

    params = jax.jit(init_params)(jax.random.key(seed))
    jax.block_until_ready(params)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads} kv_heads={cfg.num_kv_heads} "
          f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"params={n / 1e9:.3f} B ({nbytes / 2**30:.2f} GiB {cfg.param_dtype})"
          f"; text prompts only (vision front end not exercised)")
    return cfg, params


def one_chip(seed: int) -> None:
    (dev,), spec = require_tpu(1)
    cfg, params = build(seed)
    requests = make_requests(cfg, seed)
    print(f"geometry: slots={SLOTS} max_len={MAX_LEN} page_len={PAGE_LEN} "
          f"pages={NUM_PAGES}; {len(requests)} requests, prompts "
          f"{min(len(p) for p, _ in requests)}-"
          f"{max(len(p) for p, _ in requests)} tokens, "
          f"{min(n for _, n in requests)}-{max(n for _, n in requests)} new")
    streams, probe, _, _ = serve(cfg, params, requests, spec)
    check(int(jnp.argmax(probe.first_prefill)) == streams[0][0],
          "the first prefill row is not uid 0's")
    err = reference_error(cfg, params, requests[0][0], probe.first_prefill)
    print(f"logits check: uid 0 ({len(requests[0][0])}-token prompt) paged "
          f"prefill vs T.forward, max|diff|/max|ref| = {err:.6f} "
          f"(tolerance {LOGIT_TOL})")
    check(err <= LOGIT_TOL, f"logits disagree: {err} > {LOGIT_TOL}")
    _print_compiles("one chip")
    print(f"peak device memory: {_peak_bytes([dev])}")


def four_chips(seed: int) -> None:
    devices, spec = require_tpu(4)
    cfg, params = build(seed)
    requests = make_requests(cfg, seed)
    base, _, fleet, _ = serve(cfg, params, requests, spec)
    del fleet                                    # free the unsharded pool
    _print_compiles("unsharded")
    mesh = make_serve_mesh(4)
    streams, _, fleet, _ = serve(cfg, params, requests, spec, mesh=mesh)
    _print_compiles("mesh 4")
    engine = fleet.replicas[0].engine
    for path, leaf in jax.tree_util.tree_leaves_with_path(engine.cache):
        held = {s.device for s in leaf.addressable_shards}
        shard = leaf.addressable_shards[0].data.shape
        check(len(held) == 4 and shard[3] * 4 == leaf.shape[3],
              f"{jax.tree_util.keystr(path)} {leaf.shape}: shards {shard} "
              f"on {held}")
    print(f"pool: {len(jax.tree.leaves(engine.cache))} leaves, each split "
          f"over 4 devices by KV heads ({cfg.num_kv_heads} -> "
          f"{cfg.num_kv_heads // 4} per device)")
    same = sum(a == b for a, b in zip(streams, base))
    print(f"streams bit-identical to the unsharded engine: {same}/"
          f"{len(base)}")
    check(same == len(base), "mesh-sharded streams differ")
    print(f"peak device memory: {_peak_bytes(devices)}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-sharded replica on 4 chips "
                         "and the unsharded engine it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(args.seed)
    print(f"total wall: {time.perf_counter() - t0:.1f} s")
    devices = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()

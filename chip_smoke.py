"""Smoke run of the device paths on TPU chips, in one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the multi-chip paths on four chips

One chip, three phases, each checked against an oracle:

* p4mr job: a word-count ``p4mr.job`` over stores 2**20 wide, compiled for
  a one-switch fabric and run with ``plan.run(backend="jax")``; the counts
  must equal ``backend="reference"`` exactly.
* token shuffle: ``shuffle.spmd.token_shuffle`` under ``shard_map`` on a
  one-device mesh, 2**26 Zipf tokens; the received tokens and histogram
  must equal a numpy oracle, and the compiled program must hold the
  Pallas mapper kernel (``tpu_custom_call``).
* train step: three steps of ``launch.steps.make_train_step`` for
  qwen1.5-0.5b at its published widths, mesh (1, 1), ``native``, global
  batch 8, seq 512; the losses must be finite and fall on a repeated
  batch.

``--chips 4`` runs only the paths that exist across chips: word-count on a
four-switch fabric (one store per switch) against the reference, the token
shuffle on a four-device mesh against the numpy oracle, and the train step
on a (4, 1) mesh under ``s2_in_net`` and ``s3_in_net_map``, each against
``native`` after two steps.

Weights and data come from ``--seed``. The script stops at once unless
JAX's first device is a TPU, and every Pallas kernel runs compiled
(``interpret=False``). Wall times it prints include compilation: they are
set-up evidence, not measurements. The last line of its output is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from functools import partial
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

WORDCOUNT_WIDTH = 1 << 20  # store width: one count per word of the vocabulary
WORDCOUNT_WORDS = 1 << 21  # words per store; totals stay below 2**24 (exact in float32)
SHUFFLE_TOKENS = 1 << 26  # int32 tokens, all mappers together
SHUFFLE_VOCAB = 1 << 20
TRAIN_ARCH = "qwen1.5-0.5b"
TRAIN_BATCH, TRAIN_SEQ = 8, 512
# relative tolerance of a scenario's losses and gradient norms against
# native's: S2 reorders an fp32 sum; S3 also puts bf16 on the wire
SCENARIO_RTOL = {"s2_in_net": 1e-3, "s3_in_net_map": 2e-2}


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ p4mr --
def wordcount_phase(n_switches: int, *, width: int, words: int, seed: int) -> dict:
    """One store per switch, key_by over every switch, SUM, collect on d0."""
    from repro import p4mr
    from repro.compiler import CostModel
    from repro.core.topology import TorusTopology
    from repro.core.wordcount import wordcount_reference
    from repro.data.pipeline import wordcount_shards

    shards = wordcount_shards(words * n_switches, n_switches, width, seed=seed)
    hists = {f"s{i}": wordcount_reference([ws], width).astype(np.float64)
             for i, ws in enumerate(shards)}
    job = p4mr.job("wordcount")
    keyed = [job.store(f"s{i}", host=f"d{i}", items=width).key_by(n_switches)
             for i in range(n_switches)]
    keyed[0].reduce("SUM", *keyed[1:], label="COUNTS").collect("d0", label="OUT")
    # a chip is the switch: its reducer state lives in HBM, not in a
    # Tofino-sized SRAM, so the budget is 1 GiB per switch
    sess = p4mr.Session(TorusTopology(dims=(n_switches,)),
                        cost_model=CostModel(switch_memory_bytes=1 << 30))
    plan = sess.compile(job)
    got = plan.run(hists, backend="jax")["OUT"]
    want = plan.run(hists, backend="reference")["OUT"]
    if not np.array_equal(got, want):
        bad = int(np.count_nonzero(got != want))
        raise AssertionError(f"wordcount: jax differs from reference at {bad} of {width} words")
    oracle = wordcount_reference(shards, width)
    if not np.array_equal(got.astype(np.int64), oracle):
        raise AssertionError("wordcount: counts differ from the word-list oracle")
    return {"switches": n_switches, "width": width, "words": int(oracle.sum()),
            "max_count": int(oracle.max())}


# --------------------------------------------------------------- shuffle --
def shuffle_oracle(shards: np.ndarray):
    """numpy token_shuffle of (p, n) shards, each send buffer sized to the
    largest bucket: (recv (p, p*capacity), hist (p, p), capacity)."""
    from repro.kernels.ref import HASH_MULT

    p = shards.shape[0]
    h = (shards.astype(np.uint32) * np.uint32(HASH_MULT)) >> np.uint32(16)
    ids = np.where(shards >= 0, (h % np.uint32(p)).astype(np.int64), -1)
    hist = np.stack([np.bincount(ids[m][ids[m] >= 0], minlength=p) for m in range(p)])
    capacity = int(hist.max())  # the upstream sizing partition_tokens asks for
    recv = np.full((p, p, capacity), -1, np.int32)  # [reducer, mapper, slot]
    for m in range(p):
        for b in range(p):
            toks = shards[m][ids[m] == b]
            recv[b, m, : toks.size] = toks
    return recv.reshape(p, p * capacity), hist.astype(np.int32), capacity


def shuffle_phase(devices, *, n_tokens: int, seed: int) -> dict:
    """Zipf tokens, ``n_tokens / p`` per mapper, through ``token_shuffle``."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.data.pipeline import zipf_tokens
    from repro.shuffle import spmd

    p = len(devices)
    rng = np.random.Generator(np.random.Philox(key=seed))
    shards = zipf_tokens(rng, SHUFFLE_VOCAB, (p, n_tokens // p))
    want_recv, want_hist, capacity = shuffle_oracle(shards)

    mesh = Mesh(np.asarray(devices), ("all",))

    @partial(jax.shard_map, mesh=mesh, in_specs=P("all"), out_specs=(P("all"), P("all")))
    def shuffle(w):
        recv, hist = spmd.token_shuffle(w[0], "all", capacity=capacity, interpret=False)
        return recv[None], hist[None]

    x = jax.device_put(shards, NamedSharding(mesh, P("all")))
    compiled = jax.jit(shuffle).lower(x).compile()
    text = compiled.as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("shuffle: no tpu_custom_call in the compiled program")
    recv, hist = jax.device_get(compiled(x))
    if not np.array_equal(hist, want_hist):
        raise AssertionError("shuffle: histogram differs from the numpy oracle")
    if not np.array_equal(recv, want_recv):
        bad = int(np.count_nonzero(recv != want_recv))
        raise AssertionError(f"shuffle: received tokens differ from the oracle in {bad} slots")
    return {"devices": p, "tokens": int(shards.size), "capacity": capacity,
            "tpu_custom_call": True, "all_to_all": "all-to-all" in text}


# ----------------------------------------------------------------- train --
def train_metrics(cfg, mesh, scenario: str, *, steps: int, batch: int, seq: int,
                  seed: int) -> list[dict]:
    """``steps`` train steps on one repeated batch, initialised as
    ``launch.train`` does; returns each step's metrics."""
    import jax

    from repro.data.pipeline import TrainPipeline
    from repro.launch import steps as steps_lib
    from repro.launch import train
    from repro.optim import AdamW

    # the default 100-step warmup starts the rate at 1/100 of its peak; a
    # one-step warmup runs these few steps at the peak rate
    step, env, bundle = steps_lib.make_train_step(
        cfg, mesh, scenario=scenario, optimizer=AdamW(warmup_steps=1),
        global_batch=batch, seq=seq)
    args = train.parser().parse_args(["--arch", cfg.name, "--seed", str(seed)])
    params, opt_state, _ = train.init_or_restore(cfg, mesh, bundle, None, args)
    data = TrainPipeline(cfg, env, batch, seq, seed=seed).batch_at(0)
    out = []
    for _ in range(steps):
        params, opt_state, metrics = step(params, opt_state, data)
        out.append({k: float(v) for k, v in jax.device_get(metrics).items()})
    return out


def train_phase(cfg, *, batch: int, seq: int, seed: int) -> dict:
    """Three native steps on a (1, 1) mesh: finite losses that fall."""
    from repro.launch.mesh import make_mesh

    metrics = train_metrics(cfg, make_mesh((1, 1)), "native", steps=3,
                            batch=batch, seq=seq, seed=seed)
    losses = [m["loss"] for m in metrics]
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"train: non-finite loss {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"train: loss did not fall on a repeated batch: {losses}")
    return {"arch": cfg.name, "batch": batch, "seq": seq, "losses": losses,
            "grad_norms": [m["grad_norm"] for m in metrics]}


def scenario_phase(cfg, n_chips: int, *, batch: int, seq: int, seed: int) -> dict:
    """S2 and S3 against native on an (n_chips, 1) mesh: two steps each,
    losses and gradient norms within ``SCENARIO_RTOL``."""
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((n_chips, 1))
    runs = {sc: train_metrics(cfg, mesh, sc, steps=2, batch=batch, seq=seq, seed=seed)
            for sc in ("native", *SCENARIO_RTOL)}
    out = {"native": runs["native"]}
    for sc, rtol in SCENARIO_RTOL.items():
        worst = 0.0
        for got, want in zip(runs[sc], runs["native"]):
            for key in ("loss", "grad_norm"):
                if not math.isfinite(got[key]):
                    raise AssertionError(f"{sc}: non-finite {key}: {runs[sc]}")
                worst = max(worst, abs(got[key] - want[key]) / abs(want[key]))
        if worst > rtol:
            raise AssertionError(f"{sc}: differs from native by {worst:.3g} > rtol {rtol}: "
                                 f"{runs[sc]} vs {runs['native']}")
        out[sc] = {"max_rel_diff": worst, "rtol": rtol, "steps": runs[sc]}
    return out


# ------------------------------------------------------------------ main --
def run_phase(name: str, device, fn, *args, **kw) -> None:
    t0 = time.perf_counter()
    result = fn(*args, **kw)
    wall = time.perf_counter() - t0
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"[{name}] ok {json.dumps(result)}")
    log(f"[{name}] peak_bytes_in_use={peak} on device 0 so far; "
        f"wall {wall:.1f}s incl. compile (set-up evidence, not a measurement)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is {dev.platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)}", file=sys.stderr)
        return 1
    log(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)} "
        f"jax={jax.__version__}")

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(TRAIN_ARCH)
    n = args.chips
    run_phase("p4mr", dev, wordcount_phase, n, width=WORDCOUNT_WIDTH,
              words=WORDCOUNT_WORDS, seed=args.seed)
    run_phase("shuffle", dev, shuffle_phase, devices[:n], n_tokens=SHUFFLE_TOKENS,
              seed=args.seed)
    if n == 1:
        run_phase("train", dev, train_phase, cfg, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                  seed=args.seed)
    else:
        run_phase("scenarios", dev, scenario_phase, cfg, n, batch=TRAIN_BATCH,
                  seq=TRAIN_SEQ, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Driver: one switch's mapper, ``repro.shuffle.partition_tokens``, jitted.

Set-up draws a pool of word sets on the chip from the seed (``words``
log-uniform ids each), sizes the send buffer as upstream does, to the
largest bucket over the pool (rounded up to ``capacity_multiple``), and
compiles and warms up the mapper. A unit
of work maps the next set of the pool: the Pallas ``hash_partition``
kernel gives each word its bucket and the histogram, and the packer lays
the words out in a (buckets, capacity) buffer. The check compares a sample
of the window's answers, drawn from the seed, and the last one, with
``reference.wordcount.partition``, exactly.
"""
from __future__ import annotations

import functools

import numpy as np

from chipbench.reference import wordcount as ref
from chipbench.traffic import zipf

LANES = 128  # the kernel's histogram keeps one count per bucket and lane


def capacity(largest_bucket: int, traffic: dict) -> int:
    """The send buffer's slots per bucket: the largest bucket over the pool,
    rounded up to the traffic's ``capacity_multiple``, so that every seed
    gives the same shape and the same compiled program."""
    m = traffic["capacity_multiple"]
    return -(-largest_bucket // m) * m


def mapper_bytes(words: int, buckets: int, capacity: int) -> int:
    """Bytes the mapper call must move: int32 words in; the (buckets,
    capacity) buffer and the histogram out."""
    return 4 * (words + buckets * capacity + buckets)


def kernel_bytes(words: int, buckets: int) -> int:
    """Bytes the ``hash_partition`` kernel must move: int32 words in; a
    bucket id per word and the (buckets, 128) per-lane histogram out."""
    return 4 * (2 * words + buckets * LANES)


class Run:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        from repro.shuffle.spmd import partition_tokens

        p, dep = ctx.traffic, ctx.config["deployment"]
        self.n, self.buckets = p["words"], dep["servers"]
        self.device = ctx.devices[0]
        vocab = dep["vocabulary"]
        self.pool = [zipf.device_words(ctx.seed * p["pool"] + i, self.n, vocab, self.device)
                     for i in range(p["pool"])]

        @jax.jit
        def largest_bucket(words):
            h = (words.astype(jnp.uint32) * jnp.uint32(ref.HASH_MULT)) >> jnp.uint32(16)
            ids = (h % jnp.uint32(self.buckets)).astype(jnp.int32)
            return jnp.max(jnp.sum(ids[None, :] == jnp.arange(self.buckets)[:, None], axis=1))

        self.capacity = capacity(max(int(largest_bucket(w)) for w in self.pool), p)
        self.map = jax.jit(functools.partial(
            partition_tokens, num_buckets=self.buckets, capacity=self.capacity,
            interpret=self.device.platform != "tpu"))  # compiled on the chip
        jax.block_until_ready(self.map(self.pool[0]))  # compile and warm up
        ctx.log(f"capacity {self.capacity} slots per bucket; mapper compiled and warm")
        rng = np.random.Generator(np.random.Philox(key=ctx.seed))
        check = ctx.cell["check"]
        self.sample = set(rng.choice(check["sample_from"], size=check["sample"],
                                     replace=False).tolist())
        self.kept: dict[int, tuple[int, object]] = {}
        self.calls = 0
        self.work = self.n  # words mapped per unit
        self.counts = {"bytes_per_unit": mapper_bytes(self.n, self.buckets, self.capacity),
                       "kernel_bytes_per_unit": kernel_bytes(self.n, self.buckets)}

    def unit(self) -> None:
        import jax

        k = self.calls % len(self.pool)
        out = jax.block_until_ready(self.map(self.pool[k]))
        if self.calls in self.sample:
            self.kept[self.calls] = (k, out)
        self.last = (k, out)
        self.calls += 1

    def release(self) -> None:
        self.map = None
        self.kept[self.calls - 1] = self.last
        self.last = None

    def check(self):
        """Slots of the buffer and entries of the histogram that differ
        from the reference, in the worst call checked; exact, limit 0."""
        import jax

        oracle: dict[int, tuple[np.ndarray, np.ndarray]] = {}
        wrong = []
        for k, (buf, hist) in self.kept.values():
            if k not in oracle:
                oracle[k] = ref.partition(np.asarray(jax.device_get(self.pool[k])),
                                          self.buckets, self.capacity)
            want_buf, want_hist = oracle[k]
            buf, hist = jax.device_get((buf, hist))
            wrong.append(int(np.count_nonzero(buf != want_buf))
                         + int(np.count_nonzero(hist != want_hist)))
        self.kept.clear()
        compared = [{"name": "wrong_slots", "value": max(wrong), "limit": 0}]
        return compared, sum(1 for w in wrong if w)


def setup(ctx) -> Run:
    return Run(ctx)


def controls(config: dict, traffic: dict, cell: dict, seed: int, devices) -> dict:
    """The control: the mapper over int16 word ids, over the seed's pool,
    in the number the check compares (capacity sized as ``Run`` sizes it)."""
    import jax

    dep = config["deployment"]
    words = [np.asarray(jax.device_get(zipf.device_words(
        seed * traffic["pool"] + i, traffic["words"], dep["vocabulary"], devices[0])))
        for i in range(traffic["pool"])]
    slots = capacity(max(int(np.bincount(ref.bucket_ids(w, dep["servers"])).max())
                         for w in words), traffic)
    wrong = []
    for w in words:
        want_buf, want_hist = ref.partition(w, dep["servers"], slots)
        buf, hist = ref.partition_int16(w, dep["servers"], slots)
        wrong.append(int(np.count_nonzero(buf != want_buf))
                     + int(np.count_nonzero(hist != want_hist)))
    return {"control": {"wrong_slots": max(wrong)}}

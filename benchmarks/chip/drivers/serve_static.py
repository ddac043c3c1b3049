"""Serving in a closed loop of static batches.

Each batch is ``batch`` prompts of ``prompt_len`` tokens, drawn from the seed,
run through the program's ``models.decode.prefill`` and then
``new_tokens - 1`` calls of ``models.decode.decode_step`` (the first token
comes from the prefill's logits), greedy.  Both are jitted as
``launch/serve.py`` jits them: the same ``functools.partial``, the caches
donated to the decode step, the Pallas kernels on for a one-chip mesh.  The
host reads each step's new tokens back and stamps them, as a streaming server
must before it can send them.  It keeps the mix's ``ahead_steps`` steps
dispatched beyond the one it waits for, so that the chip does not wait on the
host's readback, or on a host that stands still for a moment; it stays under
what the runtime holds in flight (about 11 decode steps on a TPU v5e), or a
dispatch blocks and the tokens are read late.  Each batch ends with all of
its tokens read back, so no work spills into the next batch.

The window is whole batches, run until ``--seconds`` have passed:

- ``serve_tokens_per_s``: prompt and generated tokens of those batches over
  the window, from its start to the last token's stamp;
- ``itl_p95_ms``: 95th percentile of the gaps between successive tokens of a
  sequence, over every gap in the window.

``correct``: once the window has closed and the program's state is freed, a
sample of the finished sequences drawn from the seed goes through the plain
float32 reference, prompt and served tokens together; ``max_gap`` is the
widest gap by which a served token's reference logit lies below the
reference's best at that position.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import gc
import time
from typing import Deque, List

import numpy as np

import harness
import refmath


@dataclasses.dataclass
class Batch:
    prompts: np.ndarray  # (B, P)
    tokens: np.ndarray  # (B, N) served
    stamps: np.ndarray  # (N,) host clock when each token was read back


# the mix's sizes in the CPU tests (smoke.shrink)
SMALL = dict(batch=4, prompt_len=32, new_tokens=6, check_sequences=4, check_block=2)


class Driver:
    SPANS = ("serve.prefill", "serve.decode_step", "serve.readback")

    def __init__(self, run: harness.Run):
        self.run = run
        self.conf = run.cell.config
        t = run.cell.traffic
        self.B, self.P, self.N = t["batch"], t["prompt_len"], t["new_tokens"]
        self.ahead = t["ahead_steps"]
        self.ref = harness.load_module("reference", self.conf["reference"])
        self.counts = harness.load_module("counts", self.conf["reference"])
        self.batches: List[Batch] = []
        self.facts: dict = {}

    # -- inputs ------------------------------------------------------------
    def prompts(self, b: int) -> np.ndarray:
        """Batch ``b``'s prompts: ids in [2, vocab), a pure function of (seed, b)."""
        rng = np.random.default_rng([self.run.seed, b])
        return rng.integers(2, self.conf["vocab_size"], (self.B, self.P), dtype=np.int32)

    def weights(self):
        """The weights of ``run.seed``, made on the device in one jitted call."""
        import jax

        if not hasattr(self, "_weights"):
            self._weights = jax.jit(functools.partial(self.ref.weights, self.conf))
        return self._weights(refmath.seed_key(self.run.seed))

    # -- set-up ------------------------------------------------------------
    def load(self) -> None:
        """The weights of ``run.seed``."""
        self.params = self.weights()

    def setup(self) -> None:
        from repro.kernels import use_pallas
        from repro.launch.train import build_mesh

        mesh = build_mesh("")
        self.load()
        # as launch/serve.py: kernels on for a TPU with a one-device mesh; the
        # setting is read while the programs are traced, here
        with use_pallas(mesh.devices.flat[0].platform == "tpu" and mesh.size == 1):
            self._compile()

    def _compile(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.models import decode as dec
        from repro.models import init_params

        cfg = harness.program_config(self.conf)
        want = jax.eval_shape(functools.partial(init_params, cfg), jax.random.PRNGKey(0))
        if harness.layout(self.params) != harness.layout(want):
            raise ValueError(f"{self.conf['reference']}.weights does not give the program's "
                             f"parameter layout for {cfg.name}")
        capacity = self.P + self.N
        first = jnp.asarray(self.prompts(0))
        self.prefill = jax.jit(
            functools.partial(dec.prefill, cfg, capacity=capacity, dist=None)
        ).lower(self.params, first).compile()
        self.decode = jax.jit(functools.partial(dec.decode_step, cfg, dist=None),
                              donate_argnums=(1,))
        self.argmax = jax.jit(
            lambda logits: jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None])
        # warm every shape the window uses: prefill, argmax, a decode step
        logits, caches = self.prefill(self.params, first)
        tok = self.argmax(logits)
        logits, caches = self.decode(self.params, caches, tok, jnp.int32(self.P))
        np.asarray(self.argmax(logits))
        del logits, caches, tok

    # -- window --------------------------------------------------------------
    def _batch(self, b: int) -> Batch:
        import jax
        import jax.numpy as jnp

        span = harness.span
        pending: Deque = collections.deque()  # dispatched tokens, not yet read back
        out, stamps = [], []

        def read_oldest():
            with span("serve.readback"):
                out.append(np.asarray(pending.popleft())[:, 0])
                stamps.append(time.perf_counter())

        prompts_np = self.prompts(b)
        with span("serve.prefill"):
            prompts = jax.device_put(prompts_np)
            logits, caches = self.prefill(self.params, prompts)
            tok = self.argmax(logits)
            tok.copy_to_host_async()
        pending.append(tok)
        for i in range(self.N - 1):
            with span("serve.decode_step"):
                logits, caches = self.decode(self.params, caches, tok, jnp.int32(self.P + i))
                tok = self.argmax(logits)
                tok.copy_to_host_async()
            pending.append(tok)
            if len(pending) > self.ahead:
                read_oldest()
        while pending:
            read_oldest()
        del caches, logits
        return Batch(prompts_np, np.stack(out, axis=1), np.asarray(stamps))

    def window(self) -> None:
        run = self.run
        self.batches = []
        t0 = time.perf_counter()
        b = 0
        while True:
            with run.profiled(b == 1):
                batch = self._batch(b)
            self.batches.append(batch)
            b += 1
            if batch.stamps[-1] - t0 >= run.seconds and (not run.trace or b > 1):
                break
        self.t0, self.t1 = t0, float(self.batches[-1].stamps[-1])
        self.facts.update(
            traced_flops=self.counts.serve_flops(self.conf, self.B, self.P, self.N),
            kernels=self.counts.kernels(self.conf, self.B, self.P),
        )

    @property
    def attempted(self) -> int:
        return self.B * len(self.batches)

    @property
    def failed(self) -> int:
        V = self.conf["vocab_size"]
        return int(sum(((b.tokens < 0) | (b.tokens >= V)).any(axis=1).sum()
                       for b in self.batches))

    def end_to_end(self) -> dict:
        tokens = self.B * (self.P + self.N) * len(self.batches)
        gaps = np.concatenate([np.diff(b.stamps) for b in self.batches])
        return {"serve_tokens_per_s": tokens / (self.t1 - self.t0),
                "itl_p95_ms": 1e3 * harness.percentile(gaps, 95)}

    # -- correctness -----------------------------------------------------------
    def free(self) -> None:
        """Drop the program's state (its compiled programs hold none)."""
        self.__dict__.pop("params", None)
        gc.collect()

    def sample(self) -> np.ndarray:
        """(batch, row) of the sequences the reference reads, drawn from the seed."""
        n = self.run.cell.traffic["check_sequences"]
        rng = np.random.default_rng([self.run.seed, 2 ** 31])
        pick = rng.choice(len(self.batches) * self.B, size=min(n, self.attempted), replace=False)
        return np.stack([pick // self.B, pick % self.B], axis=1)

    def readings(self, control: bool = False) -> dict:
        """Widest gap of a served token below the reference's best logit
        (``max_gap``); with ``control``, also that of the token the reference
        computed in fp8 puts first at each position (``control_gap``)."""
        import jax
        import jax.numpy as jnp

        self.free()
        weights = self.weights()
        block = self.run.cell.traffic["check_block"]
        if not hasattr(self, "_ref"):
            self._ref = jax.jit(functools.partial(self.ref.logits, self.conf, start=self.P - 1),
                                static_argnames="mode")
        ref = self._ref
        pairs = self.sample()
        seqs = np.stack([np.concatenate([self.batches[b].prompts[r], self.batches[b].tokens[r]])
                         for b, r in pairs])
        out = {"max_gap": 0.0}
        if control:
            out["control_gap"] = 0.0
        for i in range(0, len(seqs), block):
            chunk = seqs[i:i + block]
            served = jnp.asarray(chunk[:, self.P:])
            lg = ref(weights, jnp.asarray(chunk[:, :-1]), mode="f32")  # (n, N, V)
            best = lg.max(-1)
            gap = best - jnp.take_along_axis(lg, served[..., None], -1)[..., 0]
            out["max_gap"] = max(out["max_gap"], float(gap.max()))
            if control:
                pick = ref(weights, jnp.asarray(chunk[:, :-1]), mode="fp8").argmax(-1)
                cgap = best - jnp.take_along_axis(lg, pick[..., None], -1)[..., 0]
                out["control_gap"] = max(out["control_gap"], float(cgap.max()))
        out["tokens_compared"] = int(len(seqs) * self.N)
        return out

    def checks(self) -> List[harness.Check]:
        r = self.readings()
        print(f"compared {r['tokens_compared']} served tokens of {len(self.sample())} sequences "
              f"with the reference", flush=True)
        return [harness.Check("max_gap", r["max_gap"], self.run.cell.limits["max_gap"]["limit"])]

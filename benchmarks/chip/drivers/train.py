"""Training in a closed loop of optimizer steps.

The program's ``models.steps.train_step`` is jitted as ``launch/train.py``
jits it (parameters and optimizer state donated, the Pallas kernels off: they
have no backward pass), with ``optim.adamw.init_state``.  Set-up builds that
one object, drives it through its first three steps on the feed, and hands
it on to the window: the window's step 4 continues from there.

The feed is ``feed_batches`` distinct batches drawn from the seed (a Zipf
token stream with document boundaries), kept on the device and taken in
turn.  At most two steps are in flight; the losses stay on the device until
the window has closed.

- ``train_tokens_per_s``: tokens of the steps of the window over its length,
  from the first dispatch to the last step's completion.

``correct``: the plain float32 reference follows the first three steps from
the same weights and batches.  Compared: each step's loss (``loss_gap``,
relative); the first gradient as the optimizer got it, from its first moment
after one step (``grad_gap``); the parameters' change over the three steps
(``change_gap``).  The last two per weight slice (one layer's tensor), as
|program's norm - reference's norm| over the larger of that slice's reference
norm and the median slice's, worst slice.
"""
from __future__ import annotations

import functools
import gc
import time
from typing import List

import numpy as np

import harness
import refmath

FIRST_STEPS = 3
TRACE_STEPS = 4
# the mix's sizes in the CPU tests (smoke.shrink)
SMALL = dict(batch=2, seq_len=32, feed_batches=6, mean_doc_len=8)


class Driver:
    SPANS = ("train.step",)

    def __init__(self, run: harness.Run):
        self.run = run
        self.conf = run.cell.config
        self.hp = run.cell.traffic
        self.B, self.S = self.hp["batch"], self.hp["seq_len"]
        self.ref = harness.load_module("reference", self.conf["reference"])
        self.counts = harness.load_module("counts", self.conf["reference"])
        self.losses: List = []
        self.facts: dict = {}

    # -- inputs ------------------------------------------------------------
    def batch(self, i: int) -> np.ndarray:
        """Batch ``i`` of the feed, a pure function of (seed, i): Zipf-like
        ids (rank r has p ~ 1/r^1.3), a BOS at 0 and EOS at document ends."""
        rng = np.random.Generator(np.random.Philox(key=self.run.seed, counter=np.uint64(i)))
        B, S, V = self.B, self.S, self.conf["vocab_size"]
        tokens = np.minimum(rng.zipf(1.3, size=(B, S)) + 2, V - 1).astype(np.int32)
        n_docs = max(B * S // self.hp["mean_doc_len"], 1)
        tokens[rng.integers(0, B, n_docs), rng.integers(0, S, n_docs)] = 1
        tokens[:, 0] = 0
        return tokens

    def weights(self):
        """The weights of ``run.seed``, made on the device in one jitted call."""
        import jax

        if not hasattr(self, "_weights"):
            self._weights = jax.jit(functools.partial(self.ref.weights, self.conf))
        return self._weights(refmath.seed_key(self.run.seed))

    # -- set-up ------------------------------------------------------------
    def setup(self) -> None:
        import jax

        from repro.configs.base import RunConfig
        from repro.kernels import use_pallas
        from repro.models import init_params
        from repro.models.steps import train_step
        from repro.optim import adamw

        hp = self.hp
        defaults = adamw.AdamWConfig()
        if (defaults.b1, defaults.b2, defaults.eps) != (hp["b1"], hp["b2"], hp["eps"]):
            raise ValueError(f"the program's AdamW constants {defaults} are not the traffic "
                             f"file's b1, b2, eps")
        cfg = harness.program_config(self.conf)
        run_cfg = RunConfig(model=cfg, seq_len=self.S, global_batch=self.B, n_microbatches=1,
                            learning_rate=hp["lr"], warmup_steps=hp["warmup_steps"],
                            total_steps=hp["total_steps"], weight_decay=hp["weight_decay"],
                            grad_clip=hp["grad_clip"])
        want = jax.eval_shape(functools.partial(init_params, cfg), jax.random.PRNGKey(0))
        if harness.layout(jax.eval_shape(self.weights)) != harness.layout(want):
            raise ValueError(f"{self.conf['reference']}.weights does not give the program's "
                             f"parameter layout for {cfg.name}")
        self.init_state = adamw.init_state
        self.step_fn = jax.jit(functools.partial(train_step, cfg, run_cfg, dist=None),
                               donate_argnums=(0, 1))
        self.norms = jax.jit(refmath.slice_norms)
        self.change = jax.jit(self._change)
        with use_pallas(False):  # as launch/train.py: the kernels have no backward pass
            self.load()

    def load(self) -> None:
        """Weights and feed of ``run.seed``; the first steps, with the readings
        ``correct`` compares taken from them."""
        import jax

        params = self.weights()
        opt = self.init_state(params)
        self.feed = [jax.device_put(self.batch(i)) for i in range(self.hp["feed_batches"])]
        first = []
        for k in range(FIRST_STEPS):
            with harness.span("train.step"):
                params, opt, metrics = self.step_fn(params, opt, {"tokens": self.feed[k]})
            first.append(metrics["loss"])
            if k == 0:
                grad1 = self.norms(opt.mu)
        change = self.change(params, refmath.seed_key(self.run.seed))
        self.first = {"losses": [float(x) for x in first],
                      "grad": np.asarray(grad1) / (1.0 - self.hp["b1"]),
                      "change": np.asarray(change)}
        self.params, self.opt, self.k = params, opt, FIRST_STEPS

    def _change(self, params, key):
        import jax
        import jax.numpy as jnp

        start = self.ref.weights(self.conf, key)
        return refmath.slice_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), params, start))

    # -- window --------------------------------------------------------------
    def _steps(self, n: int) -> None:
        for _ in range(n):
            with harness.span("train.step"):
                tokens = self.feed[self.k % len(self.feed)]
                self.params, self.opt, metrics = self.step_fn(self.params, self.opt,
                                                              {"tokens": tokens})
            self.losses.append(metrics["loss"])
            self.k += 1
            if len(self.losses) > 2:  # at most two steps in flight
                self.losses[-3].block_until_ready()

    def window(self) -> None:
        run = self.run
        self.losses = []
        t0 = time.perf_counter()
        chunk = 0
        while True:
            traced = run.trace and chunk == 2
            if traced:  # drain, so that the trace holds its own steps alone
                self.losses[-1].block_until_ready()
            with run.profiled(traced):
                self._steps(TRACE_STEPS if traced else 1)
                if traced:
                    self.losses[-1].block_until_ready()
            chunk += 1
            if time.perf_counter() - t0 >= run.seconds and (not run.trace or chunk > 2):
                break
        self.losses[-1].block_until_ready()
        self.t0, self.t1 = t0, time.perf_counter()
        step_flops = self.counts.train_flops(self.conf, self.B, self.S)
        self.facts["traced_flops"] = TRACE_STEPS * step_flops

    @property
    def attempted(self) -> int:
        return len(self.losses)

    @property
    def failed(self) -> int:
        return int(sum(not np.isfinite(float(x)) for x in self.losses))

    def end_to_end(self) -> dict:
        return {"train_tokens_per_s": len(self.losses) * self.B * self.S / (self.t1 - self.t0)}

    # -- correctness -----------------------------------------------------------
    def free(self) -> None:
        """Drop the program's state (its compiled programs hold none)."""
        for name in ("params", "opt", "feed"):
            self.__dict__.pop(name, None)
        gc.collect()

    def _reference(self, mode: str) -> dict:
        import jax
        import jax.numpy as jnp

        adamw = harness.load_module("reference", "adamw")
        # the control keeps its parameters in fp8 too, as the program keeps
        # them in bfloat16
        store = jax.jit(refmath.store_fp8) if mode == "fp8" else None
        params = jax.tree.map(lambda a: a.astype(jnp.float32), self.weights())
        if store is not None:
            params = jax.tree.map(store, params)
        batches = [jnp.asarray(self.batch(k)) for k in range(FIRST_STEPS)]
        loss = functools.partial(self.ref.loss, self.conf, mode=mode)
        losses, grad, params = adamw.train(loss, params, batches, self.hp, store)
        change = np.asarray(self.change(params, refmath.seed_key(self.run.seed)))
        return {"losses": losses, "grad": grad, "change": change}

    def _gaps(self, got: dict, ref: dict) -> dict:
        import jax

        names = refmath.slice_names(jax.eval_shape(self.weights))
        # slices whose gradient is nought to rounding move under Adam by
        # round-off alone: their change is not compared
        keep = ref["grad"] >= 1e-3 * np.median(ref["grad"])
        grad, gi = refmath.worst_gap(got["grad"], ref["grad"])
        change, ci = refmath.worst_gap(got["change"], ref["change"], keep)
        return {
            "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"])),
            "grad_gap": grad, "grad_worst": names[gi],
            "change_gap": change, "change_worst": names[ci],
            "slices_left_out": int((~keep).sum()),
        }

    def readings(self, control: bool = False) -> dict:
        self.free()
        ref = self._reference("f32")
        out = self._gaps(self.first, ref)
        print(f"program losses {self.first['losses']}, reference {ref['losses']}", flush=True)
        if control:
            ctrl = self._reference("fp8")
            out.update({f"control_{k}": v for k, v in self._gaps(ctrl, ref).items()})
        return out

    def checks(self) -> List[harness.Check]:
        r = self.readings()
        return [harness.Check(k, r[k], self.run.cell.limits[k]["limit"])
                for k in ("loss_gap", "grad_gap", "change_gap")]

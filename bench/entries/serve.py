"""Entry ``serve``: a request stream served by one DeepSeek-V2-kind expert.

The program is ``repro.env.serve_engine.ExpertServer`` over
``repro.models.mla``: continuous batching over ``slots`` slots of a latent
cache, one engine iteration at a time (prefill one waiting prompt into a
free slot, else decode every running slot).  The timed program is the
decode step; each prompt's bucket length has a prefill program of its own.

The request stream is fixed by the mix's ``request_seed``: prompt and
output lengths lognormal, clipped, the same for every seed, so that every
seed does the same work.  Each request runs to its drawn output length,
not to an end-of-sequence token.  The run's seed makes the weights and the
prompts' token ids, drawn over the whole vocabulary.  The loop is closed:
a finished request's slot takes the next request of the stream at once.

Set-up builds the weights on the device, compiles every prefill bucket
the stream uses and the decode step, fills the slots and runs iterations
until ``warmup_completions`` requests have finished, so that the slots'
ages are spread.  The window's ``decisions`` are the requests that
finished in it; it runs ``--seconds`` and then on to the first iteration
that finishes a request, so that it starts and ends on a finish.

The check.  The seed draws ``tracked_slots`` of the slots, in an order.
A request served in a tracked slot keeps the logits the window produced
for it: at the last prompt position, if it was prefilled in the window,
and at its first decode step, every ``kept_every``-th and its last.  In
each of the first ``checked_requests`` tracked slots (in the seed's order)
in which a request finished in the window, the last such request is
compared with the plain reference (``bench/reference_dsv2.py``): its full
forward in float32 with ``HIGHEST`` products, teacher-forced with the
program's tokens, at the same held experts.  So the check covers as many
distinct slots as requests, and some hundreds of served positions.

Set-up and the request stream are the same on every seed, so the window's
finishes fall in the same slots on every seed: about 35 of the 48 in a
10 s window on a TPU v5e.  The mix tracks every slot, so that each seed's
order finds ``checked_requests`` of them; with half the slots tracked,
about one seed in twelve found fewer.  Numbers:

* ``logit_err``: the largest |program - reference| over the reference
  logits' RMS, over the checked positions.  The program runs bfloat16
  weights, activations and cache through 27 layers; the reference widens
  the same bfloat16 weights, so their difference is activation rounding
  alone.  The limit lies between that and what float8 operands give;
* ``logit_rms_err``: the RMS of the difference over the reference's RMS,
  the worst position: the same comparison, averaged over the vocabulary,
  so that a fault that moves every logit a little shows beside one that
  moves a few a lot;
* ``unchecked``: checked requests wanted and not found (limit 0): a check
  that compared nothing does not pass.

The control is the reference with every product's operands rounded to
float8_e4m3fn, in the program's place.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference_dsv2, serve_counts


def published(cfg: dict) -> dict:
    """The source's keys, routing over all its routed experts: the file's
    ``n_routed_experts`` counts those held here."""
    return dict(cfg, n_routed_experts=cfg["n_routed_experts_published"])


def model_config(cfg: dict):
    """The program's config of the cell: the published model, holding
    ``held_expert_ids``."""
    from repro.configs import deepseek_v2_lite
    return deepseek_v2_lite.from_hf(
        published(cfg), held_experts=tuple(cfg["held_expert_ids"]),
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["param_dtype"])


def lengths(spec: dict, rng, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


class Entry:
    def __init__(self, cfg: dict, traffic: dict):
        from repro.env import serve_engine

        self.cfg, self.traffic = cfg, traffic
        self.mcfg = model_config(cfg)
        self.slots, self.max_len = traffic["slots"], traffic["max_len"]
        rng = np.random.default_rng(traffic["request_seed"])
        n = traffic["stream_requests"]
        self.prompt_len = lengths(traffic["prompt_tokens"], rng, n)
        self.output_len = lengths(traffic["output_tokens"], rng, n)
        over = self.prompt_len + self.output_len > self.max_len
        if over.any():
            raise ValueError(f"{int(over.sum())} requests of the stream "
                             f"outgrow max_len {self.max_len}")
        self.buckets = sorted({serve_engine._bucket(int(p), self.max_len)
                               for p in self.prompt_len})
        self._take = jax.jit(lambda logits, slot: logits[slot])

    def timed_program(self):
        """(jitted decode step, its argument shapes), built as the server
        builds it."""
        from repro.env import serve_engine
        from repro.models import model
        _, decode = serve_engine.make_steps(self.mcfg, self.max_len)
        params = jax.eval_shape(lambda: model.init_params(
            jax.random.PRNGKey(0), self.mcfg))
        cache = jax.eval_shape(lambda: model.init_cache(
            self.mcfg, self.slots, self.max_len))
        return decode, (params, cache,
                        jax.ShapeDtypeStruct((self.slots,), jnp.int32))

    # -- run ---------------------------------------------------------------

    def _submit(self) -> None:
        from repro.env.serve_engine import Request
        i = self.next_rid % len(self.prompt_len)
        toks = self.tok_rng.integers(0, self.cfg["vocab_size"],
                                     int(self.prompt_len[i]))
        self.server.submit(Request(rid=self.next_rid, tokens=toks,
                                   max_new=int(self.output_len[i]),
                                   ignore_eos=True))
        self.next_rid += 1

    def setup(self, seed: int) -> None:
        from repro.env.serve_engine import ExpertServer
        from repro.models import model
        self.seed = seed
        # a second set-up in one process (bench/readings.py) frees the
        # first one's weights before it builds its own
        self.params = self.server = self.kept = None
        key = jax.random.PRNGKey(seed)
        self.params = jax.jit(lambda k: model.init_params(k, self.mcfg))(key)
        self.server = ExpertServer("dsv2lite", self.mcfg, self.params,
                                   slots=self.slots, max_len=self.max_len)
        # compile every prefill bucket the stream uses, then the decode
        for b in self.buckets:
            self.server.prefill(np.zeros((b,), np.int32), 0)
        self.tok_rng = np.random.default_rng(seed)
        self.next_rid = 0
        self.tracked = [int(s) for s in np.random.default_rng(seed).permutation(
            self.slots)[:self.traffic["tracked_slots"]]]
        for _ in range(self.slots):
            self._submit()
        done = 0
        while done < self.traffic["warmup_completions"]:
            finished = self.server.step()
            done += len(finished)
            for _ in finished:
                self._submit()
        # the window keeps decode logits of single slots
        jax.block_until_ready(self._take(
            jnp.zeros((self.slots, self.mcfg.vocab_padded), jnp.float32), 0))
        self.completed = done

    def _loop(self, seconds: float, capture: bool) -> dict:
        srv, log = self.server, self.server.iteration_log
        ann = jax.profiler.TraceAnnotation
        finished_n, failed, steps = 0, 0, 0
        t0 = time.perf_counter()
        end = t0 + seconds
        finished = []
        # set-up ends on an iteration that finished a request; so does the
        # window, and a count of finishes then carries no partial request
        while not (finished and time.perf_counter() >= end):
            if capture:
                before = {rid: (r.slot, len(r.generated))
                          for rid, r in srv.active.items()
                          if r.slot in self.tracked}
            with ann("bench.serve.step"):
                finished = srv.step()
            steps += 1
            if capture:
                self._capture(log[-1], before)
            for r in finished:
                finished_n += 1
                failed += int(any(t >= self.cfg["vocab_size"]
                                  for t in r.generated))
                if capture and r.rid in self.kept:
                    self._finish(r)
                self._submit()
        return {"wall_s": time.perf_counter() - t0, "finished": finished_n,
                "failed": failed, "steps": steps}

    def _capture(self, last: dict, before: dict) -> None:
        """Keep the logits of the requests in tracked slots at their kept
        positions (``at``: the count of tokens generated before)."""
        if last["kind"] == "prefill":
            rid = last["rid"]
            if self.server.active[rid].slot in self.tracked:
                self.kept[rid] = {"at": [0], "logits": [self.server.logits]}
            return
        every = self.traffic["kept_every"]
        for rid, (slot, n) in before.items():
            k = self.kept.setdefault(rid, {"at": [], "logits": []})
            n_out = int(self.output_len[rid % len(self.output_len)])
            if n == 1 or n % every == 0 or n == n_out - 1:
                k["at"].append(n)
                k["logits"].append(self._take(self.server.logits, slot))

    def _finish(self, r) -> None:
        """The slot's last request finished in the window replaces the one
        before, whose logits are dropped."""
        old = self.done_in.get(r.slot)
        if old is not None:
            del self.kept[old]
        self.done_in[r.slot] = r.rid
        self.kept[r.rid]["done"] = r

    def window(self, seconds: float) -> dict:
        srv = self.server
        srv.iteration_log.clear()
        self.kept, self.done_in = {}, {}
        load0 = np.asarray(srv.cache["load"])
        out = self._loop(seconds, capture=True)
        log = srv.iteration_log
        dec = [e for e in log if e["kind"] == "decode"]
        pre = [e for e in log if e["kind"] == "prefill"]
        load = np.asarray(srv.cache["load"]) - load0
        flops = (sum(serve_counts.prefill_flops(self.cfg, e["x"]) for e in pre)
                 + sum(serve_counts.decode_flops(self.cfg, e["n"],
                                                 e["x"] + e["n"])
                       for e in dec))
        from repro.env import serve_engine
        fit = serve_engine.calibrate(srv)
        print(f"[bench] calibration over the window's iterations (k1 s per "
              f"prompt token, k2 s per cached token of a decode step): "
              f"{json.dumps(fit)}", file=sys.stderr, flush=True)
        per_step = load / max(len(dec), 1)
        self.completed += out["finished"]
        return {"wall_s": out["wall_s"], "decisions": out["finished"],
                "attempted": out["finished"], "failed": out["failed"],
                "iterations": out["steps"], "decode_steps": len(dec),
                "prefills": len(pre),
                "prefill_tokens": int(sum(e["x"] for e in pre)),
                "decode_tokens": int(sum(e["n"] for e in dec)),
                "model_flops": flops,
                "expert_load": float(np.mean(per_step.max(1)
                                             / np.maximum(per_step.mean(1),
                                                          1e-9))),
                "calibration": fit}

    def traced_stretch(self, seconds: float) -> None:
        """More iterations, run as the window runs them, for the profiler."""
        self.server.iteration_log.clear()
        self._loop(seconds, capture=False)
        dec = [e for e in self.server.iteration_log if e["kind"] == "decode"]
        self.traced_positions = (np.mean([e["x"] + e["n"] for e in dec])
                                 if dec else 0.0)

    def trace_prepare(self) -> None:
        pass

    def trace_extras(self) -> dict:
        return {"decode_program": "serve_decode",
                "prefill_program": "serve_prefill",
                "decode_bytes": serve_counts.decode_weight_bytes(
                    self.cfg, self.slots)
                + serve_counts.latent_cache_bytes(self.cfg,
                                                  self.traced_positions)}

    def release(self) -> None:
        """Drop the cache before the reference runs; the weights stay, the
        reference reads them."""
        self.server = None

    def episode_counts(self) -> dict:
        return {"completed": self.completed, "next_request": self.next_rid}

    def counts(self) -> dict:
        return {"held_params": serve_counts.held_params(self.cfg)}

    # -- check ---------------------------------------------------------------

    def checked(self) -> list:
        """(prompt and output tokens, positions, program logits) of the
        checked requests: in each of the first ``checked_requests``
        tracked slots in which a request finished in the window, the last
        such request."""
        slots = [s for s in self.tracked if s in self.done_in]
        self.finished_slots = len(slots)
        out = []
        for slot in slots[:self.traffic["checked_requests"]]:
            k = self.kept[self.done_in[slot]]
            r = k["done"]
            seq = np.concatenate([r.tokens, r.generated[:-1]]).astype(np.int32)
            at = np.asarray(k["at"]) + len(r.tokens) - 1
            logits = np.stack([np.asarray(x)[:self.cfg["vocab_size"]]
                               for x in k["logits"]])
            out.append((seq, at, logits))
        return out

    def check(self, control: bool = False) -> dict:
        hf = published(self.cfg)
        held = self.cfg["held_expert_ids"]
        ref = reference_dsv2.Reference(hf, held)
        ctl = (reference_dsv2.Reference(hf, held, rnd=reference_dsv2.float8)
               if control else None)
        cases = self.checked()
        out = {"unchecked": self.traffic["checked_requests"] - len(cases)}
        # one padded length and one count of positions: causal attention
        # keeps the checked positions as they are, and the reference
        # compiles once
        n_at = max(len(at) for _, at, _ in cases) if cases else 0
        for seq, at, got in cases:
            padded = np.zeros((self.max_len,), np.int32)
            padded[:len(seq)] = seq
            at_n = np.pad(at, (0, n_at - len(at)), mode="edge")
            want = np.asarray(ref.logits(self.params, padded, at_n))[:len(at)]
            _worst(out, "logit_err", got, want)
            if ctl is not None:
                _worst(out, "control.logit_err", np.asarray(
                    ctl.logits(self.params, padded, at_n))[:len(at)], want)
        out["checked_positions"] = sum(len(at) for _, at, _ in cases)
        out["finished_slots"] = self.finished_slots
        return out


def _worst(out: dict, name: str, got, want) -> None:
    """The worst position's ``name`` (max |diff| / RMS) and its RMS twin."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    rms = np.sqrt(np.mean(want ** 2, axis=-1))
    diff = got - want
    pairs = ((name, np.abs(diff).max(-1) / rms),
             (name.replace("logit_err", "logit_rms_err"),
              np.sqrt(np.mean(diff ** 2, -1)) / rms))
    for key, v in pairs:
        v = float(np.nan_to_num(v, nan=np.inf).max())
        out[key] = max(out.get(key, v), v)

"""A serving cell: the program's continuous-batching engine
(:class:`repro.serve.ServeEngine`) on the int8 KV pool, driven open or
closed loop by the mix's requests.

Set-up makes the weights from the seed, builds the engine and warms up
one request of each prompt length the mix uses (each compiles its own
whole-prompt prefill) plus the decode step.  The window submits requests
as they fall due and steps the engine.  Time to first token is counted
from when a request was due, not from when the loop got round to
submitting it.  After the window the engine runs on, submitting nothing,
until every request due in the window has its first token (a minute at
most); then the plain reference reads the logits of a seeded sample of
the finished requests, the longest among them.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import loadgen, weights
from bench.readers import pct

DRAIN_S = 60.0


class Cell:
    def __init__(self, config: dict, mix: dict, seed: int, *,
                 fault: str = ""):
        self.config, self.mix, self.seed = config, mix, seed
        self.model = config["model"]
        self.fault = fault
        self.eng = None
        self.params = None

    def setup(self) -> None:
        from repro import configs
        from repro.core.policy import PrecisionPolicy
        from repro.models import transformer as T
        from repro.serve import EngineOptions, SamplerConfig, ServeEngine
        from repro.serve import metrics as serve_metrics

        smoke = self.config.get("preset") == "smoke"
        cfg = (configs.get_smoke if smoke else configs.get)(
            self.config["repro_config"])
        pol = PrecisionPolicy(**self.config["policy"])
        kind = self.config["reference"]
        self.params = weights.make_params(kind, self.model, self.seed)
        weights.check_program_config(self.config, cfg)
        want = jax.eval_shape(lambda: T.init_params(cfg,
                                                    jax.random.PRNGKey(0)))
        if jax.tree.map(lambda x: (x.shape, x.dtype), want) != \
                jax.tree.map(lambda x: (x.shape, x.dtype), self.params):
            raise ValueError("configuration file and program disagree on "
                             "the parameter shapes")
        sv = self.config["serve"]
        opts = EngineOptions(cache_bits=sv["cache_bits"],
                             sampler_cfg=SamplerConfig(kind=sv["sampler"]),
                             seed=self.seed & 0x7FFFFFFF)
        self.eng = ServeEngine(cfg, pol, self.params,
                               max_slots=self.mix["slots"],
                               max_len=self.mix["max_len"], options=opts)
        if self.fault == "alter_token":
            self._plant_altered_token()
        for r in loadgen.warmup_requests(self.mix):
            self.eng.submit(r.prompt, max_new=r.max_new)
        self.eng.run()

        stamps = self.stamps = {}

        class Stamped(serve_metrics.ServeMetrics):
            def on_token(self, uid):
                stamps.setdefault(uid, []).append(time.perf_counter())
                super().on_token(uid)

        self.eng.metrics = Stamped()

    def _plant_altered_token(self):
        """Planted fault: at each decode step one row's sampled token,
        the rows taken in turn, is replaced by its successor id where it
        is produced; every request of some length gets altered tokens."""
        eng = self.eng
        inner = eng._decode
        calls = [0]

        def altered(*args):
            nxt, bad, rate, pool = inner(*args)
            row = calls[0] % nxt.shape[0]
            calls[0] += 1
            return nxt.at[row].set((nxt[row] + 1) % self.mix["vocab_ids"]), \
                bad, rate, pool

        eng._decode = altered

    # -- window -------------------------------------------------------------
    def window(self, seconds: float, poll) -> None:
        """Submit as requests fall due and step the engine for
        ``seconds``; ``poll`` runs once an iteration (the harness's
        profiler)."""
        eng, mix = self.eng, self.mix
        steps = []                       # (t_start, t_end) per engine step
        submitted = {}                   # uid -> (request, due, t_submit)

        def submit(req, due):
            with jax.profiler.TraceAnnotation("bench:submit"):
                uid = eng.submit(req.prompt, max_new=req.max_new)
            submitted[uid] = (req, due, time.perf_counter())
            return uid

        def step():
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:engine_step"):
                eng.step()
            steps.append((a, time.perf_counter()))

        def pending():
            return any(eng.status(u) is None for u in submitted)

        t0 = time.perf_counter()
        t_end = t0 + seconds
        if mix["loop"] == "open":
            reqs = loadgen.open_loop(mix, self.seed, seconds)
            i = 0
            while True:
                poll()
                now = time.perf_counter()
                if now >= t_end:
                    break
                while i < len(reqs) and t0 + reqs[i].due_s <= now:
                    submit(reqs[i], t0 + reqs[i].due_s)
                    i += 1
                if pending():
                    step()
                else:
                    nxt = t0 + reqs[i].due_s if i < len(reqs) else t_end
                    with jax.profiler.TraceAnnotation("bench:wait"):
                        time.sleep(max(0.0, min(nxt, t_end)
                                       - time.perf_counter()))
        else:
            queues = loadgen.closed_loop(mix, self.seed,
                                         mix["per_client"])
            nxt_i = [0] * len(queues)
            owner = {}
            for c, q in enumerate(queues):
                owner[submit(q[0], t0)] = c
                nxt_i[c] = 1
            while time.perf_counter() < t_end:
                poll()
                step()
                for uid in [u for u, c in owner.items()
                            if eng.status(u) is not None]:
                    c = owner.pop(uid)
                    if nxt_i[c] < len(queues[c]):
                        owner[submit(queues[c][nxt_i[c]],
                                     time.perf_counter())] = c
                        nxt_i[c] += 1
        self.submitted, self._steps = submitted, steps
        self._t0, self._t_close = t0, time.perf_counter()
        self._step = step

    def drain(self) -> dict:
        """Run on past the window, submitting nothing, until every
        request due in it has its first token (``DRAIN_S`` at most)."""
        eng, uids = self.eng, list(self.submitted)
        while (any(u not in self.stamps and eng.status(u) is None
                   for u in uids)
               and time.perf_counter() < self._t_close + DRAIN_S):
            self._step()
        return self._summarise(uids, self._t0, self._t_close, self._steps,
                               time.perf_counter())

    def _summarise(self, uids, t0, t_close, steps, t_drained):
        from repro.serve import RequestStatus
        eng, window = self.eng, t_close - t0
        ttft, waits, lags, gaps = [], [], [], []
        tokens = failed = 0
        for u in uids:
            req, due, t_sub = self.submitted[u]
            st = self.stamps.get(u, [])
            status = eng.status(u)
            bad = status not in (None, RequestStatus.OK) or not st
            failed += bad
            # a request with no first token waited at least until now
            ttft.append((st[0] if st else t_drained) - due)
            lags.append(t_sub - due)
            tr = eng.metrics.traces.get(u)
            if tr is not None and tr.t_admit is not None:
                waits.append(tr.t_admit - due)
            tokens += sum(1 for t in st if t <= t_close)
            gaps += [b - a for a, b in zip(st, st[1:]) if b <= t_close]
        # decode rows and cached positions per engine step, for the
        # per-layer rooflines: token i >= 1 of a request came from a
        # decode step that read its prompt plus i positions
        decode, prefill = [], []
        for u in uids:
            L = len(self.submitted[u][0].prompt)
            for i, t in enumerate(self.stamps.get(u, [])):
                (prefill if i == 0 else decode).append((t, L, L + i))
        e2e = {"serve_tokens_per_s": tokens / window,
               "itl_p95_ms": 1e3 * (pct(gaps, 95) or 0.0)}
        if self.mix["loop"] == "open":
            e2e["ttft_p90_ms"] = 1e3 * (pct(ttft, 90) or 0.0)
        return {"attempted": len(uids), "failed": failed, "metrics": e2e,
                "ctx": {"window_s": window, "t0": t0, "t_close": t_close,
                        "steps": steps, "decode_tokens": decode,
                        "prefills": prefill, "queue_waits": waits,
                        "submit_lags": lags, "model": self.model,
                        "kv_bits": self.config["serve"]["cache_bits"]}}

    def release(self) -> None:
        status = {u: self.eng.status(u) for u in self.submitted}
        # a zero step budget resolves what is still in flight and hands
        # back every request's tokens
        out = self.eng.run(max_steps=0)
        self.results = {u: (np.asarray(self.submitted[u][0].prompt),
                            np.asarray(out.get(u, []), np.int32), status[u])
                        for u in self.submitted}
        self.eng = self._step = None
        gc.collect()

    # -- check --------------------------------------------------------------
    def sample(self):
        """A seeded sample of the finished requests, the one with the
        most served tokens among them."""
        from repro.serve import RequestStatus
        done = sorted(u for u, (_, out, st) in self.results.items()
                      if st is RequestStatus.OK and out.size)
        if not done:
            return []
        n = min(self.mix["check_requests"], len(done))
        longest = max(done, key=lambda u: self.results[u][1].size)
        rest = [u for u in done if u != longest]
        g = loadgen.rng(self.seed, 5)
        pick = g.choice(len(rest), size=n - 1, replace=False).tolist() \
            if n > 1 else []
        return [longest] + [rest[i] for i in sorted(pick)]

    def gaps(self, kv_bits: int = 0) -> dict:
        """Over the sample: the widest gap by which a served token's
        reference logit lies below the reference's best
        (``logit_gap``), and the share of served tokens that are not the
        reference's first choice (``mismatch_share``).  With ``kv_bits``
        the control: the token the lower-precision reference puts first
        stands where the served one did."""
        ref = weights.reference(self.config["reference"])
        plain = ref.make_logits(self.model)
        low = ref.make_logits(self.model, kv_bits) if kv_bits else None
        T = self.mix["max_len"]
        widest, served, missed = 0.0, 0, 0
        for u in self.sample():
            prompt, out, _ = self.results[u]
            L, n = prompt.size, out.size
            seq = np.zeros(T, np.int32)
            seq[:L], seq[L:L + n] = prompt, out
            lg = np.asarray(plain(self.params, jnp.asarray(seq),
                                  jnp.int32(L)))[L - 1:L - 1 + n]
            if low is None:
                tok = out
            else:
                tok = np.asarray(low(self.params, jnp.asarray(seq),
                                     jnp.int32(L)))[L - 1:L - 1 + n].argmax(-1)
            gap = lg.max(-1) - lg[np.arange(n), tok]
            widest = max(widest, float(gap.max()))
            missed += int(np.sum(gap > 0))
            served += n
        return {"logit_gap": widest,
                "mismatch_share": missed / served if served else 1.0,
                "served_tokens_checked": served}

    def numbers(self, ref: dict) -> dict:
        return {"logit_gap": ref["logit_gap"],
                "mismatch_share": ref["mismatch_share"]}

    def reference(self) -> dict:
        return self.gaps()

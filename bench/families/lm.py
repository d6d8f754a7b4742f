"""Adapter from the benchmark onto the program's LM serve path.

Builds the program's configuration from ``bench/configs/<config>.json``
(and checks that every size the file states is the size that runs), pads
the reference's weights into the program's parameter layout in one jitted
call, serves through ``repro.serve.ServeEngine`` with
``attn_impl="pallas"`` and ``cache_layout="kernel"`` (whole-prompt
prefill through the ALiBi flash kernel, paged insert, paged
``flash_decode``), warms every program the cell's traffic reaches, and
compares the served tokens with ``bench/reference/lm.py``.
"""
from __future__ import annotations

import functools
import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, traffic
from bench.common import BenchError

REF = common.load_module("reference", "lm")

# config "model" keys that must equal the program's ArchConfig fields
_FIELDS = {"n_layers": "n_layers", "d_model": "d_model", "n_heads": "n_heads",
           "n_kv_heads": "n_kv_heads", "head_dim": "resolved_head_dim",
           "d_ff": "d_ff", "vocab": "vocab", "dtype": "dtype"}
CONTENT_STREAM, SAMPLE_STREAM, WEIGHT_STREAM = 10, 20, 30


def program_config(cfg: dict):
    from repro.configs import get_config, smoke_config
    prog = cfg["program"]
    get = smoke_config if prog.get("preset") == "smoke" else get_config
    pcfg = get(prog["arch"]).replace(
        attn_impl=prog.get("attn_impl", "pallas"), cache_layout="kernel",
        **prog.get("overrides", {}))
    for key, field in _FIELDS.items():
        if key in cfg["model"] and cfg["model"][key] != getattr(pcfg, field):
            raise BenchError(f"{cfg['name']}: the file states {key}="
                             f"{cfg['model'][key]} but the program runs "
                             f"{field}={getattr(pcfg, field)}")
    if pcfg.family != "dense" or pcfg.bias_kind != "alibi":
        raise BenchError(f"{cfg['name']}: the lm family serves dense ALiBi "
                         f"models, not {pcfg.family}/{pcfg.bias_kind}")
    return pcfg


def to_program(w: dict, pcfg) -> dict:
    """The reference's weights in the program's layout: heads padded with
    zeros to ``heads_padded`` (a zero head adds nothing: its output
    projection rows are zero), vocabulary rows padded with zeros, gate and
    up projections fused on a trailing axis."""
    hp, vp = pcfg.heads_padded, pcfg.vocab_padded
    lw = w["layers"]
    h = lw["wq"].shape[2]

    def heads(x, axis):
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, hp - h)
        return jnp.pad(x, pad)

    return {
        "embed": jnp.pad(w["embed"], ((0, vp - w["embed"].shape[0]), (0, 0))),
        "final_norm": w["final_norm"],
        "layers": {
            "ln1": lw["ln1"],
            "attn": {"wq": heads(lw["wq"], 2), "wk": heads(lw["wk"], 2),
                     "wv": heads(lw["wv"], 2), "wo": heads(lw["wo"], 1),
                     "slopes": heads(lw["slopes"], 1)},
            "ln2": lw["ln2"],
            "mlp": {"wi": jnp.stack([lw["w_gate"], lw["w_up"]], -1),
                    "wo": lw["w_down"]},
        },
    }


class Cell:
    """One LM cell: weights, engine, warm-up, load, comparison."""

    def __init__(self, cfg: dict, mix: dict, seed: int, limits: dict,
                 log=print):
        self.cfg, self.mix, self.seed, self.limits = cfg, mix, seed, limits
        self.log = log
        self.eng = cfg["engine"]
        self.pcfg = program_config(cfg)
        self.dims = REF.dims_of(cfg)
        self.content = common.seed_rng(seed, CONTENT_STREAM)
        self.engine = None
        self.prompts = {}
        self.served = []

    # -- set-up ----------------------------------------------------------
    def setup(self) -> None:
        from repro.models import get_model
        from repro.models.common import is_pdef
        from repro.serve import ServeEngine
        self.model = get_model(self.pcfg)
        t = time.perf_counter()
        make = jax.jit(lambda k: to_program(
            REF.init_weights(k, self.dims, self.cfg.get("init")), self.pcfg))
        key = common.seed_key(self.seed, WEIGHT_STREAM)
        shapes = jax.tree.map(lambda p: p.shape, self.model.template(),
                              is_leaf=is_pdef)
        got = jax.tree.map(lambda a: a.shape, jax.eval_shape(make, key))
        if got != shapes:
            raise BenchError("weights do not match the program's parameter "
                             "template")
        self.params = jax.block_until_ready(make(key))
        self.log(f"[setup] weights made on the device in "
                 f"{time.perf_counter() - t:.3f} s")
        e = self.eng
        self.engine = ServeEngine(
            self.model, self.params, max_len=e["max_len"],
            n_slots=e["n_slots"], prefill_len=e["prefill_len"],
            page_size=e["page_size"], n_pages=e["n_pages"],
            pages_per_slot=e["pages_per_slot"],
            page_reservation=e["page_reservation"])
        t = time.perf_counter()
        self._warm()
        self.log(f"[setup] warm-up (every program the traffic reaches) "
                 f"{time.perf_counter() - t:.3f} s")

    def _warm(self) -> None:
        """Reach every program the cell's traffic can: serve an admission
        wave of each size (prefill, insert, sampling, and the eager
        scatters shaped by the wave), then run the engine's decode program
        once for each power-of-two page bucket up to the longest context
        the mix holds (a step at context c runs bucket
        pow2(ceil((c + 1) / page_size)), capped at pages_per_slot), with
        every slot idle and its result dropped."""
        e, eng = self.eng, self.engine
        for w in range(1, e["n_slots"] + 1):
            rids = [eng.submit(self._tokens(8), 2) for _ in range(w)]
            eng.run()
            self._ok(rids)
        longest = (min(traffic.largest(self.mix, "prompt"), e["prefill_len"])
                   + traffic.largest(self.mix, "output") - 1)
        need = math.ceil((longest + 1) / e["page_size"])
        be, b = eng.backend, 1
        while True:
            cap = min(b, e["pages_per_slot"])
            jax.block_until_ready(be._decode(be.params, be._cache,
                                             be._last_tok, max_pages=cap))
            if b >= need:
                break
            b *= 2

    def _ok(self, rids) -> None:
        bad = [self.engine.status(r) for r in rids
               if self.engine.status(r) != "OK"]
        if bad:
            raise BenchError(f"warm-up requests ended {bad}")

    def _tokens(self, n: int) -> np.ndarray:
        return self.content.integers(0, self.dims["V"], n).astype(np.int32)

    # -- serving ---------------------------------------------------------
    def submit(self, spec: dict, rec) -> int:
        prompt = self._tokens(min(spec["prompt"], self.eng["prefill_len"]))
        times = rec.times
        rid = self.engine.submit(
            prompt, spec["output"],
            on_token=lambda _tok: times.append(time.perf_counter()))
        self.prompts[rid] = prompt
        rec.spec["prompt"] = int(prompt.size)
        return rid

    def step(self):
        return self.engine.step()

    def status(self, rid: int) -> str:
        return self.engine.status(rid)

    def work(self, ended) -> dict:
        """Token work is read from the records' token times instead."""
        return {}

    @property
    def occupancy(self) -> int:
        return self.engine.occupancy

    def idle(self) -> bool:
        return self.engine.occupancy == 0 and len(self.engine.scheduler) == 0

    # -- after the window --------------------------------------------------
    def finish(self, window) -> None:
        """Take the sample the comparison needs (the longest request that
        finished in the window, then others drawn from the seed until the
        sample holds ``sample_tokens`` served tokens), then free the
        program's state so the reference has the chip."""
        done = [r for r in window.records
                if r.status == "OK" and window.inside(r.finished)]
        if not done:
            raise BenchError("no request finished in the window")
        done.sort(key=lambda r: -(r.spec["prompt"] + r.spec["output"]))
        rng = common.seed_rng(self.seed, SAMPLE_STREAM)
        order = [done[0]] + [done[1:][i] for i in
                             rng.permutation(len(done) - 1)]
        want, got = self.limits.get("sample_tokens", 256), 0
        for rec in order:
            if got >= want:
                break
            toks = np.asarray(self.engine.result(rec.rid), np.int32)
            self.served.append((self.prompts[rec.rid], toks))
            got += toks.size
        self.engine = self.params = None
        self.prompts = {}
        gc.collect()

    def check(self, control: bool = False) -> dict:
        """The widest gap by which a served token's reference logit lies
        below the reference's best, over the sample; with ``control`` the
        same gap for the token the fp8 control, put in the program's place,
        ranks first at each of those positions, held to the same limit."""
        w = jax.jit(functools.partial(REF.init_weights, dims=self.dims,
                                      init=self.cfg.get("init")))(
            common.seed_key(self.seed, WEIGHT_STREAM))
        t_pad = -(-(traffic.largest(self.mix, "prompt")
                    + traffic.largest(self.mix, "output")) // 128) * 128
        worst, n = 0.0, 0
        for prompt, served in self.served:
            seq = np.zeros((t_pad,), np.int32)
            seq[:prompt.size] = prompt
            seq[prompt.size:prompt.size + served.size] = served
            gaps = REF.served_gaps(w, jnp.asarray(seq), prompt.size,
                                   served.size, fp8=control)
            worst = max(worst, float(jnp.max(gaps)))
            n += served.size
        self.log(f"[check] {'control' if control else 'program'}: "
                 f"{len(self.served)} requests, {n} served tokens")
        return {"max_logit_gap": (worst,
                                  self.limits.get("max_logit_gap", 0.0))}

"""Adapter from the benchmark onto the program's serve path for LMs whose
layers mix kinds (Mamba2 and attention, ``cfg.layer_kinds``).

Builds the program's configuration from ``bench/configs/<config>.json``,
whose top-level keys are the model's published ``config.json`` (checked
against the sizes that run), lays the reference's weights out as the
program's parameters one tensor at a time (so the chip never holds two
copies of the model), serves through ``repro.serve.ServeEngine`` with
``attn_impl="pallas"`` and ``cache_layout="kernel"`` (whole-prompt prefill
waves through the flash prefill kernel and the SSD scan kernel, paged
insert, paged ``flash_decode`` in its bias-free mode and the one-token
SSM update), warms every program the cell's traffic reaches, and compares
the served tokens with ``bench/reference/hybrid_lm.py``.

The session is the ``lm`` family's, but for the configuration, the weights,
the warm-up of waves (a wave takes at most ``prefill_rows`` requests), the
sample and the comparison.
"""
from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, traffic
from bench.common import BenchError

LM = common.load_module("families", "lm")
REF = common.load_module("reference", "hybrid_lm")

# published key -> the program's ArchConfig field that must equal it
_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads",
           "shared_intermediate_size": "d_ff", "vocab_size": "vocab",
           "mamba_n_heads": "ssm_heads", "mamba_d_head": "ssm_head_dim",
           "mamba_d_state": "ssm_state", "mamba_d_conv": "conv_width",
           "mamba_expand": "ssm_expand", "mamba_chunk_size": "ssd_chunk",
           "mamba_conv_bias": "conv_bias",
           "embedding_multiplier": "embedding_multiplier",
           "residual_multiplier": "residual_multiplier",
           "logits_scaling": "logits_scaling",
           "attention_multiplier": "attention_multiplier",
           "rms_norm_eps": "norm_eps", "dtype": "dtype"}


def program_config(cfg: dict):
    from repro.configs import get_config, smoke_config
    prog = cfg["program"]
    get = smoke_config if prog.get("preset") == "smoke" else get_config
    pcfg = get(prog["arch"]).replace(
        attn_impl=prog.get("attn_impl", "pallas"), cache_layout="kernel",
        **prog.get("overrides", {}))
    for key, field in _FIELDS.items():
        if key in cfg and cfg[key] != getattr(pcfg, field):
            raise BenchError(f"{cfg['name']}: the file states {key}="
                             f"{cfg[key]} but the program runs "
                             f"{field}={getattr(pcfg, field)}")
    if list(pcfg.layer_kinds) != list(cfg["layer_types"]):
        raise BenchError(f"{cfg['name']}: the program's layer kinds are not "
                         f"the published layer_types")
    if (cfg["mamba_n_groups"] != 1
            or cfg["position_embedding_type"] != "nope"
            or pcfg.bias_kind != "none" or pcfg.ssm_heads_padded
            != pcfg.ssm_heads or pcfg.heads_padded != pcfg.n_heads):
        raise BenchError(f"{cfg['name']}: the hybrid_lm family serves "
                         f"NoPE attention, one SSM group and no padding")
    return pcfg


def leaves(kind: str, name: str, a, pcfg) -> dict:
    """The program's parameter leaves made of one reference tensor, by
    their path under ``params["kinds"][kind]``. Norm weights w become the
    program's scale w - 1 (exact in bfloat16 for w in [0.5, 2])."""
    n, d = a.shape[0], pcfg.d_model
    hm, p, N = pcfg.ssm_heads, pcfg.ssm_head_dim, pcfg.ssm_state
    di, f = hm * p, pcfg.d_ff
    h, kv, hd = pcfg.n_heads, pcfg.n_kv_heads, pcfg.resolved_head_dim

    def scale(x):
        return (x.astype(jnp.float32) - 1.0).astype(x.dtype)

    if name in ("ln1", "ln2"):
        return {(name,): scale(a)}
    if name == "w_in":            # [gate | up] -> (2, d, f)
        return {("mlp", "wi"): a.reshape(n, d, 2, f).transpose(0, 2, 1, 3)}
    if name == "w_out":
        return {("mlp", "wo"): a}
    m = ("mixer",)
    if kind == "attention":
        shape = {"wq": (n, d, h, hd), "wk": (n, d, kv, hd),
                 "wv": (n, d, kv, hd), "wo": (n, h, hd, d)}[name]
        return {m + (name,): a.reshape(shape)}
    if name == "in_proj":         # columns [z, x, B, C, dt]
        cut = np.cumsum([di, di, N, N])
        z, x, b, c, dt = jnp.split(a, cut, axis=-1)
        return {m + ("in_z",): z.reshape(n, d, hm, p),
                m + ("in_x",): x.reshape(n, d, hm, p),
                m + ("in_b",): b, m + ("in_c",): c, m + ("in_dt",): dt}
    if name in ("conv_w", "conv_b"):   # channels [x, B, C]
        out = "conv_w" if name == "conv_w" else "conv_b"
        return {m + (out,): a[..., :di].reshape(a.shape[:-1] + (hm, p)),
                m + (out.replace("conv", "conv_bc"),): a[..., di:]}
    if name == "norm":
        return {m + ("gate_norm",): scale(a).reshape(n, hm, p)}
    if name == "out_proj":
        return {m + ("out",): a.reshape(n, hm, p, d)}
    return {m + (name,): a}       # a_log, dt_bias, d_skip


def to_program(w: dict, pcfg, convert=None) -> dict:
    """The reference's weights as the program's parameters. ``convert``
    wraps each tensor's conversion (set-up passes a jitted one and drops
    each source tensor as it goes)."""
    convert = convert or (lambda fn, a: fn(a))
    vp = pcfg.vocab_padded

    def nest(tree, path, val):
        for k in path[:-1]:
            tree = tree.setdefault(k, {})
        tree[path[-1]] = val

    params = {
        "embed": convert(lambda e: jnp.pad(e, ((0, vp - e.shape[0]),
                                               (0, 0))), w.pop("embed")),
        "final_norm": convert(lambda x: (x.astype(jnp.float32) - 1.0
                                         ).astype(x.dtype), w.pop("final_norm")),
        "kinds": {},
    }
    for kind in sorted(set(pcfg.layer_kinds)):
        src = w.pop(kind)
        for name in sorted(src):
            out = convert(functools.partial(leaves, kind, name, pcfg=pcfg),
                          src.pop(name))
            for path, val in out.items():
                nest(params["kinds"].setdefault(kind, {}), path, val)
    return params


def co_resident(done: list, ok: list, want: int, rng) -> list:
    """Up to ``want`` requests (loop ``Record``s) that held a slot at one
    instant, so each held a slot of its own: the longest (prompt and
    answer) of ``done``, then others of ``ok`` (every request that ended
    OK, the ramp's too) live at its first token, drawn by ``rng``. A fault
    confined to one slot's state then reaches the sample from any of
    several slots."""
    first = max(done, key=lambda r: r.spec["prompt"] + r.spec["output"])
    t = first.times[0]
    others = [r for r in ok
              if r is not first and r.times[0] <= t <= r.finished]
    return [first] + [others[i] for i in
                      rng.permutation(len(others))[:want - 1]]


class Cell(LM.Cell):
    """One hybrid LM cell: weights, engine, warm-up, load, comparison."""

    def __init__(self, cfg: dict, mix: dict, seed: int, limits: dict,
                 log=print):
        self.cfg, self.mix, self.seed, self.limits = cfg, mix, seed, limits
        self.log = log
        self.eng = cfg["engine"]
        self.pcfg = program_config(cfg)
        self.dims = REF.dims_of(cfg)
        self.content = common.seed_rng(seed, LM.CONTENT_STREAM)
        self.engine = None
        self.prompts = {}
        self.served = []

    def _weights(self):
        return jax.jit(functools.partial(REF.init_weights, dims=self.dims,
                                         init=self.cfg.get("init")))(
            common.seed_key(self.seed, LM.WEIGHT_STREAM))

    def setup(self) -> None:
        from repro.models import get_model
        from repro.models.common import is_pdef
        from repro.serve import ServeEngine
        self.model = get_model(self.pcfg)
        t = time.perf_counter()

        def convert(fn, a):
            out = jax.jit(fn)(a)
            a.delete()                # one tensor in two layouts at most
            return out

        self.params = to_program(self._weights(), self.pcfg, convert)
        shapes = jax.tree.map(lambda p: p.shape, self.model.template(),
                              is_leaf=is_pdef)
        if jax.tree.map(lambda a: a.shape, self.params) != shapes:
            raise BenchError("weights do not match the program's parameter "
                             "template")
        jax.block_until_ready(self.params)
        self.log(f"[setup] weights made on the device in "
                 f"{time.perf_counter() - t:.3f} s")
        e = self.eng
        self.engine = ServeEngine(
            self.model, self.params, max_len=e["max_len"],
            n_slots=e["n_slots"], prefill_len=e["prefill_len"],
            page_size=e["page_size"], n_pages=e["n_pages"],
            pages_per_slot=e["pages_per_slot"],
            page_reservation=e["page_reservation"],
            prefill_rows=e["prefill_rows"])
        t = time.perf_counter()
        self._warm()
        self.log(f"[setup] warm-up (every program the traffic reaches) "
                 f"{time.perf_counter() - t:.3f} s; engine counters "
                 f"{self.engine.counters()}")

    def _warm(self) -> None:
        """Every program the traffic reaches: admission waves of each size
        a wave can take (1 .. prefill_rows), then the decode program for
        each power-of-two page bucket up to the longest context the mix
        holds, every slot idle and the result dropped."""
        import math
        e, eng = self.eng, self.engine
        for w in range(1, e["prefill_rows"] + 1):
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

    def finish(self, window) -> None:
        """Take the sample the comparison needs (``co_resident``: the
        longest request that finished in the window and others that shared
        the engine with it, ``sample_requests`` in all, every served token
        of each), then free the program's state so the reference has the
        chip."""
        ok = [r for r in window.records if r.status == "OK"]
        done = [r for r in ok if window.inside(r.finished)]
        if not done:
            raise BenchError("no request finished in the window")
        rng = common.seed_rng(self.seed, LM.SAMPLE_STREAM)
        for rec in co_resident(done, ok, self.limits["sample_requests"],
                               rng):
            toks = np.asarray(self.engine.result(rec.rid), np.int32)
            self.served.append((self.prompts[rec.rid], toks))
        self.engine = self.params = None
        self.prompts = {}
        gc.collect()

    def check(self, control: bool = False) -> dict:
        """The widest gap by which a served token's reference logit lies
        below the reference's best, over the sample; with ``control`` the
        same gap for the token the fp8 control, put in the program's place,
        ranks first at each of those positions, held to the same limit."""
        w = self._weights()
        window = traffic.largest(self.mix, "output")
        t_pad = -(-(min(traffic.largest(self.mix, "prompt"),
                        self.eng["prefill_len"]) + window) // 128) * 128
        worst, n = 0.0, 0
        for prompt, served in self.served:
            seq = np.zeros((t_pad,), np.int32)
            seq[:prompt.size] = prompt
            seq[prompt.size:prompt.size + served.size] = served
            gaps = REF.served_gaps(w, jnp.asarray(seq), prompt.size,
                                   served.size, self.dims, window,
                                   fp8=control)
            worst = max(worst, float(jnp.max(gaps)))
            n += served.size
        self.log(f"[check] {'control' if control else 'program'}: "
                 f"{len(self.served)} requests, {n} served tokens")
        return {"max_logit_gap": (worst,
                                  self.limits.get("max_logit_gap", 0.0))}

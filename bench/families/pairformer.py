"""Adapter from the benchmark onto the program's Pairformer serve path.

Serves complexes through ``repro.serve.ServeEngine`` (its
``PairBatchBackend``) with ``attn_impl="pallas"``: admission runs the
trunk on a wave padded to ``max_len`` residues and caches each block's
truncated-SVD bias factors per slot; every engine step is one refinement
step of every live complex through the factored-bias ragged kernel. No
``on_token`` sink is set: it would fetch each complex's single rep to the
host every step. A complex's bias factors and its single rep after its
first two steps are compared with ``bench/reference/pairformer.py``.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, traffic
from bench.common import BenchError

REF = common.load_module("reference", "pairformer")

_FIELDS = {"n_layers": "n_layers", "d_model": "d_model", "n_heads": "n_heads",
           "head_dim": "resolved_head_dim", "d_ff": "d_ff",
           "d_pair": "d_pair", "bias_rank": "bias_rank", "dtype": "dtype"}
CONTENT_STREAM, SAMPLE_STREAM, WEIGHT_STREAM = 10, 20, 30


def program_config(cfg: dict):
    from repro.configs import get_config, smoke_config
    prog = cfg["program"]
    get = smoke_config if prog.get("preset") == "smoke" else get_config
    pcfg = get(prog["arch"]).replace(attn_impl=prog.get("attn_impl", "pallas"),
                                     **prog.get("overrides", {}))
    for key, field in _FIELDS.items():
        if key in cfg["model"] and cfg["model"][key] != getattr(pcfg, field):
            raise BenchError(f"{cfg['name']}: the file states {key}="
                             f"{cfg['model'][key]} but the program runs "
                             f"{field}={getattr(pcfg, field)}")
    if pcfg.family != "pairformer" or pcfg.bias_mode != "flashbias":
        raise BenchError(f"{cfg['name']}: not a factored-bias Pairformer")
    return pcfg


class Cell:
    """One Pairformer cell: weights, engine, warm-up, load, comparison."""

    def __init__(self, cfg: dict, mix: dict, seed: int, limits: dict,
                 log=print):
        self.cfg, self.mix, self.seed, self.limits = cfg, mix, seed, limits
        self.log = log
        self.eng = cfg["engine"]
        self.pcfg = program_config(cfg)
        self.dims = REF.dims_of(cfg)
        self.content = common.seed_rng(seed, CONTENT_STREAM)
        self.engine = None
        self.feats, self.sizes, self.seen = {}, {}, set()
        self.results = []

    def setup(self) -> None:
        from repro.models import get_model
        from repro.models.common import is_pdef
        from repro.serve import ServeEngine
        self.model = get_model(self.pcfg)
        t = time.perf_counter()
        make = jax.jit(lambda k: REF.init_weights(k, self.dims,
                                                   self.cfg.get("init")))
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
        self.engine = ServeEngine(self.model, self.params,
                                  max_len=self.eng["max_len"],
                                  n_slots=self.eng["n_slots"])
        t = time.perf_counter()
        self._warm()
        self.log(f"[setup] warm-up (every program the traffic reaches) "
                 f"{time.perf_counter() - t:.3f} s")

    def _warm(self) -> None:
        """One complex through admission and two steps compiles the wave
        programs. The engine then reads each finished complex back with a
        static slice of its slot's row as long as the complex, which
        compiles once per (slot, size), so each of those is read here for
        every size the mix holds."""
        eng = self.engine
        rid = eng.submit(self._feats(min(8, self.eng["max_len"])), 2)
        eng.run()
        if eng.status(rid) != "OK":
            raise BenchError(f"warm-up complex ended {eng.status(rid)}")
        sizes = sorted(set(traffic.stratified(self.mix["sizes"]["n_res"],
                                              self.mix["pool"])))
        s = eng.backend._cache["s"]
        for slot in range(self.eng["n_slots"]):
            for n in sizes:
                np.asarray(s[slot, :n], np.float32)

    def _feats(self, n: int) -> np.ndarray:
        return self.content.standard_normal((n, REF.FEAT)).astype(np.float32)

    def submit(self, spec: dict, rec) -> int:
        feats = self._feats(spec["n_res"])
        rid = self.engine.submit(feats, spec["steps"])
        self.feats[rid] = feats
        self.sizes[rid] = spec["n_res"]
        return rid

    def step(self):
        return self.engine.step()

    def status(self, rid: int) -> str:
        return self.engine.status(rid)

    def work(self, ended) -> dict:
        """The complexes this engine step refined (every slot live after it
        and every complex that ended in it) and those it admitted (seen for
        the first time): their ids and their residue counts."""
        stepped = [st.req.rid for st in self.engine._live.values()]
        stepped += list(ended)
        admitted = [r for r in stepped if r not in self.seen]
        self.seen.update(admitted)
        size = self.sizes
        return {"stepped_rid": stepped,
                "stepped": [size[r] for r in stepped],
                "admitted": [size[r] for r in admitted]}

    @property
    def occupancy(self) -> int:
        return self.engine.occupancy

    def idle(self) -> bool:
        return self.engine.occupancy == 0 and len(self.engine.scheduler) == 0

    def finish(self, window) -> None:
        """Follow the complexes queued when the window closes (up to
        ``sample_complexes``) through the engine's own steps: keep each
        one's bias factors and single rep after its first step (the step
        of its admission), and its single rep after its second step. Then
        free the program's state."""
        eng = self.engine
        follow = [r.rid for r in eng.scheduler.queued()][
            :self.limits.get("sample_complexes", 2)]
        if not follow:
            raise BenchError("no complex queued when the window closed")
        seen = {rid: {} for rid in follow}
        keys = ("s", "phi_q", "phi_k")
        while any("s2" not in v for v in seen.values()):
            eng.step()
            want = [(slot, st) for slot, st in eng._live.items()
                    if st.req.rid in seen and st.generated in (1, 2)]
            if not want:
                continue
            cache = jax.device_get({k: eng.backend._cache[k] for k in keys})
            for slot, st in want:
                got, n = seen[st.req.rid], self.sizes[st.req.rid]
                rep = np.asarray(cache["s"][slot, :n], np.float32)
                if st.generated == 1:
                    got["s1"] = rep
                    got["phi"] = (cache["phi_q"][:, slot, :n],
                                  cache["phi_k"][:, slot, :n])
                else:
                    got["s2"] = rep
        self.results = [(self.feats[rid], seen[rid]) for rid in follow]
        self.engine = self.params = None
        self.feats = {}
        gc.collect()

    def check(self, control: bool = False) -> dict:
        """For each followed complex, three relative errors against the
        reference: ``bias`` — each block's bias as the program's factors give
        it (phi_q phi_k^T) against the reference's rank-R bias (Frobenius,
        worst block and head); ``trunk`` — the single rep after admission
        and its first step against the reference's trunk and one reference
        step; ``step`` — the single rep after the second step against one
        reference step from the rep after the first (teacher-forced from
        the program's own rep). With ``control`` the same numbers for the
        fp8 control put in the program's place (its own trunk, bias and
        steps), held to the same limits."""
        w = jax.jit(lambda k: REF.init_weights(k, self.dims,
                                               self.cfg.get("init")))(
            common.seed_key(self.seed, WEIGHT_STREAM))
        big_n, rank = self.dims["N"], min(self.dims["R"], self.dims["N"])
        worst = {f"max_{k}_rel_err": 0.0 for k in ("bias", "trunk", "step")}

        def pad(x):
            out = np.zeros((big_n,) + x.shape[1:], np.float32)
            out[:x.shape[0]] = x
            return jnp.asarray(out)

        def rel(a, b):
            return float(np.linalg.norm(a - b) / np.linalg.norm(b))

        for feats, got in self.results:
            n = feats.shape[0]

            def run(fp8):
                s, bias = REF.trunk(w, pad(feats), n, rank, fp8=fp8)
                reps = [s, pad(got["s1"])]
                steps = [np.asarray(REF.step(w, x, bias, n, fp8=fp8),
                                    np.float64)[:n] for x in reps]
                return np.asarray(bias, np.float64)[:, :, :n, :n], steps

            ref_b, ref = run(False)
            if control:
                bias, out = run(True)
            else:
                pq, pk = (np.asarray(x, np.float64) for x in got["phi"])
                bias = np.einsum("lnhr,lmhr->lhnm", pq, pk)
                out = [got["s1"], got["s2"]]
            per_head = (np.linalg.norm(bias - ref_b, axis=(2, 3))
                        / np.linalg.norm(ref_b, axis=(2, 3)))
            for k, v in (("bias", float(per_head.max())),
                         ("trunk", rel(out[0], ref[0])),
                         ("step", rel(out[1], ref[1]))):
                worst[f"max_{k}_rel_err"] = max(worst[f"max_{k}_rel_err"], v)
        self.log(f"[check] {'control' if control else 'program'}: "
                 f"{len(self.results)} complexes followed from admission "
                 f"through two steps (n_res "
                 f"{[f.shape[0] for f, _ in self.results]})")
        return {k: (v, self.limits.get(k, 0.0)) for k, v in worst.items()}

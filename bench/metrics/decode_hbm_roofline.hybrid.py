"""Share of the HBM roofline the whole decode step of an interleaved
Mamba2 / attention model reaches.

Needed bytes, per decode step the window ran: every weight once in bf16
(the tied embedding once, as the output head); for every token decoded in
the window (one live slot in one step), its slot's recurrent state read
and written in each Mamba layer (SSM state f32, conv tails bf16) and, in
each attention layer, the keys and values of its real context read (bf16,
published kv heads and head size) and its own row written. Padding (idle
slots, lanes past the head size) is not needed. The sum over the chip's
819 GB/s, over the decode program's device time (the union of its
operations' intervals, ``jit_decode``); steps are its executions that
start in the window."""
import re

from bench import trace as tr

BF16, F32 = 2, 4
PROGRAM = r"^jit_decode\b"


def weight_bytes(m: dict) -> int:
    d, f, v = m["hidden_size"], m["shared_intermediate_size"], m["vocab_size"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // h
    hm, p, s, w = (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"],
                   m["mamba_d_conv"])
    di = hm * p
    mlp = 3 * d * f + 2 * d                         # MLP and the two norms
    mamba = (d * (2 * di + 2 * s + hm) + (w + 1) * (di + 2 * s)
             + 3 * hm + di + di * d)
    attn = d * (h + 2 * kv) * hd + h * hd * d
    kinds = m["layer_types"]
    n_m = sum(k == "mamba" for k in kinds)
    return BF16 * (v * d + d + n_m * (mamba + mlp)
                   + (len(kinds) - n_m) * (attn + mlp))


def state_bytes(m: dict) -> int:
    """Recurrent state one slot holds over the Mamba layers."""
    hm, p, s, w = (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"],
                   m["mamba_d_conv"])
    per = hm * p * s * F32 + (w - 1) * (hm * p + 2 * s) * BF16
    return sum(k == "mamba" for k in m["layer_types"]) * per


def token_bytes(m: dict, context: int) -> int:
    """One token's decode reads and writes beyond the weights."""
    kv, hd = m["num_key_value_heads"], m["hidden_size"] // m[
        "num_attention_heads"]
    n_a = sum(k == "attention" for k in m["layer_types"])
    return 2 * state_bytes(m) + n_a * 2 * kv * hd * BF16 * (context + 1)


def steps(trace) -> int:
    lo, hi = trace.window
    dev = sorted(trace.modules)[0]
    rx = re.compile(PROGRAM)
    return sum(1 for e in trace.modules[dev]
               if lo <= e.start < hi and rx.search(e.name))


def read(ctx):
    if ctx.trace is None or not ctx.trace.modules:
        return None
    t = tr.busy_s(ctx.trace, module=PROGRAM)
    n = steps(ctx.trace)
    ctxs = ctx.window.decode_contexts()
    if not t or not n or not ctxs:
        return None
    b = n * weight_bytes(ctx.cfg) + sum(token_bytes(ctx.cfg, c)
                                        for c in ctxs)
    ctx.note(f"decode_hbm_roofline.hybrid: {n} decode steps, {len(ctxs)} "
             f"tokens decoded in the window")
    return ctx.roofline("decode_hbm_roofline.hybrid", 0.0, b, t)

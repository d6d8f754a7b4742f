"""The pair admission program, lowered for TPU, solves each layer's
pair-bias SVDs in one batched eigensolve: every ``Eigh`` custom call takes
the wave's (slots, heads, max_len, max_len) batch, and none takes a
256 x 256 leaf of the divide and conquer that jax's TPU ``svd`` maps over
the batch one matrix at a time. Lowering needs no chip."""
import re

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.models import get_model
from repro.models.common import init_params
from repro.serve import PairBatchBackend

N_SLOTS, MAX_LEN = 4, 384


def test_admission_eigensolves_take_the_whole_wave():
    cfg = smoke_config("pairformer_lite")
    model = get_model(cfg)
    params = init_params(model.template(), jax.random.PRNGKey(0))
    be = PairBatchBackend(model, params, max_len=MAX_LEN, n_slots=N_SLOTS)
    feats = jax.ShapeDtypeStruct((N_SLOTS, MAX_LEN, 64), jnp.float32)
    lengths = jax.ShapeDtypeStruct((N_SLOTS,), jnp.int32)
    text = be._prefill.trace(params, feats, lengths, None,
                             max_len=MAX_LEN).lower(
        lowering_platforms=("tpu",)).as_text()
    operands = re.findall(r"custom_call @Eigh\(.*?: \((tensor<[^>]*>)\)",
                          text)
    wave = f"tensor<{N_SLOTS}x{cfg.n_heads}x{MAX_LEN}x{MAX_LEN}xf32>"
    assert 1 <= len(operands) <= cfg.n_layers
    assert set(operands) == {wave}
    assert "256x256" not in text

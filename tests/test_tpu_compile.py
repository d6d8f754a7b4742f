"""Compile the main-path Pallas kernels for a described TPU v5e, at real widths.

Nothing runs: the TPU compiler that ships with JAX compiles for a v5e:2x2
topology that is described, not attached. That refuses what interpret mode
accepts — block shapes off the (8, 128) tiling, a Mosaic kernel left for
GSPMD to partition — so these cases guard the chip path without a chip.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and the
worker that runs this file keeps it until it exits. Every test here compiles
in its own process; none starts a child that would need the library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.dist import Rules, make_mesh, use_mesh_rules
from repro.kernels import ops
from repro.kernels.ssd_scan import ssd_scan_fwd
from repro.models import get_model
from repro.models.common import abstract_params
from repro.serve.backend import TokenDecodeBackend
from repro.statcheck.mesh_rules import collective_sizes

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, kernel: str) -> str:
    """Compile ``fn`` and check that its Pallas kernel keeps the
    ``pallas_call`` name ``kernel``: the custom call is named after it, and
    the device trace tells kernels apart by it."""
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, "no Pallas kernel in the program"
    names = re.findall(r"%([\w-]+?)(?:\.\d+)? = [^\n]*tpu_custom_call", hlo)
    assert names and set(names) == {kernel}, names
    return hlo


def test_alibi_causal_prefill_gpt2(one_chip):
    # gpt2_alibi_15b prefill: 64 padded heads of 32 (lane-padded to 128)
    q = _arg((1, 64, 512, 32), jnp.bfloat16, one_chip)
    slopes = _arg((64,), jnp.float32, one_chip)
    _compile(lambda q, k, v, s: ops.flash_attention(
        q, k, v, slopes=s, mask_kind="causal", impl="pallas",
        layout="bhsd"), q, q, q, slopes, kernel="flashbias_attn")


def test_alibi_contiguous_decode_gpt2(one_chip):
    # kernel-layout contiguous cache: (B, KVH, S, hd lane-padded)
    q = _arg((4, 1, 64, 32), jnp.bfloat16, one_chip)
    kv = _arg((4, 64, 512, 128), jnp.bfloat16, one_chip)
    lengths = _arg((4,), jnp.int32, one_chip)
    slopes = _arg((64,), jnp.float32, one_chip)
    _compile(lambda q, k, v, n, s: ops.flash_decode(
        q, k, v, n, slopes=s, impl="pallas"),
        q, kv, kv, lengths, slopes, kernel="flash_decode")


@pytest.mark.parametrize("heads,kv_heads,head_dim", [
    (64, 64, 32),      # gpt2_alibi_15b: MHA, hd 32 -> 128
    (32, 8, 160),      # stablelm_12b: GQA g 4 -> 8, hd 160 -> 256
], ids=["gpt2", "stablelm_gqa"])
def test_paged_decode_shared_phi_slab(one_chip, heads, kv_heads, head_dim):
    b, ps, n_pages, p_slot = 4, 128, 12, 3
    hd_pad = -(-head_dim // 128) * 128
    q = _arg((b, 1, heads, head_dim), jnp.bfloat16, one_chip)
    pool = _arg((kv_heads, n_pages, ps, hd_pad), jnp.bfloat16, one_chip)
    lengths = _arg((b,), jnp.int32, one_chip)
    table = _arg((b, p_slot), jnp.int32, one_chip)
    phi_q = _arg((b, 1, heads, 2), jnp.float32, one_chip)
    slab = _arg((n_pages, ps, 128), jnp.float32, one_chip)
    _compile(lambda q, k, v, n, t, pq, pk: ops.flash_decode(
        q, k, v, n, phi_q=pq, phi_k=pk, impl="pallas", page_table=t,
        max_pages=p_slot),
        q, pool, pool, lengths, table, phi_q, slab,
        kernel="flash_decode_paged")


def test_pairformer_phi_ragged(one_chip):
    # pairformer_lite: 4 heads of 96, rank-96 factors, n_res 384, ragged
    qkv = _arg((2, 384, 4, 96), jnp.bfloat16, one_chip)
    phi = _arg((2, 384, 4, 96), jnp.float32, one_chip)
    lengths = _arg((2,), jnp.int32, one_chip)
    _compile(lambda q, k, v, pq, pk, n: ops.flash_attention(
        q, k, v, pq, pk, impl="pallas", lengths=n),
        qkv, qkv, qkv, phi, phi, lengths, kernel="flashbias_attn_ragged")


def test_ssd_scan(one_chip):
    # mamba2_130m: 24 heads of 64, state 128, chunk 256
    x = _arg((1, 24, 512, 64), jnp.float32, one_chip)
    dt = _arg((1, 24, 512, 1), jnp.float32, one_chip)
    a = _arg((24,), jnp.float32, one_chip)
    bc = _arg((1, 1, 512, 128), jnp.float32, one_chip)
    _compile(lambda x, dt, a, b, c: ssd_scan_fwd(x, dt, a, b, c, chunk=256),
             x, dt, a, bc, bc, kernel="ssd_scan")


def test_ssd_scan_granite_prefill(one_chip):
    # granite-4.0-h-micro: 64 heads of 64, state 128, chunk 256, a 2048-
    # token prompt with an initial state and the final state out, B and C
    # shared by every head through the index map
    x = _arg((1, 2048, 64, 64), jnp.float32, one_chip)
    dt = _arg((1, 2048, 64), jnp.float32, one_chip)
    a = _arg((64,), jnp.float32, one_chip)
    bc = _arg((1, 2048, 128), jnp.float32, one_chip)
    h0 = _arg((1, 64, 64, 128), jnp.float32, one_chip)
    _compile(lambda x, dt, a, b, c, h0: ops.ssd_scan(
        x, dt, a, b, c, chunk=256, h0=h0, impl="pallas"),
        x, dt, a, bc, bc, h0, kernel="ssd_scan")


def test_sharded_paged_decode(topo):
    """gpt2 paged decode on a (data 2, model 2) mesh: the kernel runs per
    shard inside shard_map, and nothing pool-sized is all-gathered."""
    _sharded_paged_decode(topo, stacked=False)


def test_sharded_paged_decode_layer_stack(topo):
    """The same with the pools a two-layer stack read at a traced layer
    index, as the model's decode step passes them: the layer axis stays
    whole on every device."""
    _sharded_paged_decode(topo, stacked=True)


def _sharded_paged_decode(topo, stacked):
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    b, h, ps, n_pages, p_slot = 4, 64, 128, 12, 3

    def arg(shape, dtype, *spec):
        return _arg(shape, dtype, NamedSharding(mesh, P(*spec)))

    q = arg((b, 1, h, 32), jnp.bfloat16, "data", None, "model", None)
    if stacked:
        pool = arg((2, h, n_pages, ps, 128), jnp.bfloat16, None, "model")
    else:
        pool = arg((h, n_pages, ps, 128), jnp.bfloat16, "model")
    layer = arg((), jnp.int32) if stacked else None
    lengths = arg((b,), jnp.int32, "data")
    table = arg((b, p_slot), jnp.int32, "data")
    phi_q = arg((b, 1, h, 2), jnp.float32, "data", None, "model", None)
    slab = arg((n_pages, ps, 128), jnp.float32)
    with use_mesh_rules(mesh, Rules()):
        hlo = _compile(lambda q, k, v, n, t, pq, pk, layer: ops.flash_decode(
            q, k, v, n, phi_q=pq, phi_k=pk, impl="pallas", page_table=t,
            max_pages=p_slot, layer=layer),
            q, pool, pool, lengths, table, phi_q, slab, layer,
            kernel="flash_decode_paged")
    pool_shard = (h // 2) * n_pages * ps * 128
    assert collective_sizes(hlo).get("all-gather", 0) < pool_shard


_INSTR = re.compile(
    r"^\s*(?:ROOT )?%([\w.-]+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(", re.M)


def _roots(hlo: str) -> dict:
    """Opcode of each computation's ROOT instruction, by computation name."""
    roots, comp = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.-]+) ", line)
        if head:
            comp = head.group(1)
        root = re.match(r"^\s*ROOT %[\w.-]+ = .*?\S ([\w-]+)\(", line)
        if root and comp:
            roots[comp] = root.group(1)
    return roots


def test_paged_decode_step_updates_pool_in_place(one_chip):
    """gpt2_alibi_15b's paged decode step as the serve backend jits it (32
    padded heads in 128 lanes, 32 pages of 128; 4 of its 48 layers): the
    donated K/V pools alias the program's outputs, and no instruction but
    an in-place scatter of token rows produces an array shaped like the
    whole pool or like one layer's pool: no copy, slice or restack of it."""
    cfg = get_config("gpt2_alibi_15b").replace(
        n_layers=4, n_heads=25, n_kv_heads=25, head_dim=64,
        attn_impl="pallas")
    model = get_model(cfg)
    n_slots, n_pages, ps, per_slot = 8, 32, 128, 6
    params = abstract_params(model.template())
    be = TokenDecodeBackend(model, params, max_len=768, n_slots=n_slots,
                            prefill_len=256, page_size=ps, n_pages=n_pages,
                            pages_per_slot=per_slot,
                            page_reservation="whole")
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        n_slots, n_pages, ps, per_slot))
    pool = (cfg.n_layers, 32, n_pages, ps, 128)
    assert cache["pages_k"].shape == pool

    def place(x):
        return _arg(x.shape, x.dtype, one_chip)

    hlo = be._decode.lower(
        jax.tree.map(place, params), jax.tree.map(place, cache),
        _arg((n_slots, 1), jnp.int32, one_chip),
        max_pages=4).compile().as_text()

    kernels = re.findall(r"%([\w-]+?)(?:\.\d+)? = [^\n]*tpu_custom_call",
                         hlo)
    assert kernels and set(kernels) == {"flash_decode_paged"}, kernels
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry_computation",
                        hlo)
    aliased = {int(n) for n in re.findall(r"\((\d+), \{",
                                          aliases.group(1) if aliases else "")}
    for leaf in ("pages_k", "pages_v"):
        param = re.search(
            r"%cache__" + leaf + r"__\S* = \S+ parameter\((\d+)\)", hlo)
        assert param and int(param.group(1)) in aliased, leaf

    roots = _roots(hlo)
    shapes = {pool, pool[1:]}
    moved = []
    for name, dims, opcode in _INSTR.findall(hlo):
        shape = tuple(int(d) for d in dims.split(",") if d and d != "1")
        if shape not in shapes or opcode in ("parameter",
                                             "get-tuple-element", "scatter"):
            continue
        called = re.search(
            r"%" + re.escape(name) + r" = [^\n]*calls=%([\w.-]+)", hlo)
        if opcode == "fusion" and roots.get(called.group(1)) == "scatter":
            continue
        moved.append(f"{name} {opcode} {shape}")
    assert not moved, moved


def test_hybrid_decode_step_updates_state_in_place(one_chip):
    """granite-4.0-h-micro's paged decode step as the serve backend jits
    it, one period of its layer pattern (9 Mamba2 layers and one attention
    layer, published widths, 32 slots): the donated KV pools and the SSM
    state and conv stacks alias the program's outputs, nothing but an
    in-place update produces an array shaped like a whole stack, and its
    only Pallas kernel keeps the name ``paged_decode_roofline.hybrid``
    selects it by."""
    cfg = get_config("granite_4_0_h_micro")
    cfg = cfg.replace(n_layers=10, layer_kinds=cfg.layer_kinds[:10],
                      attn_impl="pallas")
    model = get_model(cfg)
    n_slots, n_pages, ps, per_slot = 32, 64, 128, 2
    params = abstract_params(model.template())
    be = TokenDecodeBackend(model, params, max_len=256, n_slots=n_slots,
                            prefill_len=128, page_size=ps, n_pages=n_pages,
                            pages_per_slot=per_slot,
                            page_reservation="whole", prefill_rows=1)
    cache = jax.eval_shape(lambda: model.init_paged_cache(
        n_slots, n_pages, ps, per_slot))
    assert cache["ssm_h"].shape == (9, n_slots, 64, 64, 128)
    assert cache["pages_k"].shape == (1, 8, n_pages, ps, 128)

    hlo = be._decode.lower(      # weights served in bf16
        jax.tree.map(lambda x: _arg(x.shape, jnp.bfloat16, one_chip),
                     params),
        jax.tree.map(lambda x: _arg(x.shape, x.dtype, one_chip), cache),
        _arg((n_slots, 1), jnp.int32, one_chip),
        max_pages=per_slot).compile().as_text()
    kernels = re.findall(r"%([\w-]+?)(?:\.\d+)? = [^\n]*tpu_custom_call",
                         hlo)
    assert kernels and set(kernels) == {"flash_decode_paged"}, kernels
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry_computation",
                        hlo)
    aliased = {int(n) for n in re.findall(r"\((\d+), \{",
                                          aliases.group(1) if aliases else "")}
    for leaf in ("pages_k", "pages_v", "ssm_h", "conv_x", "conv_bc"):
        param = re.search(
            r"%cache__" + leaf + r"__\S* = \S+ parameter\((\d+)\)", hlo)
        assert param and int(param.group(1)) in aliased, leaf
    stacks = {tuple(d for d in v.shape if d != 1)
              for k, v in cache.items() if k in ("ssm_h", "pages_k")}
    moved = [f"{name} {opcode}" for name, dims, opcode in _INSTR.findall(hlo)
             if tuple(int(d) for d in dims.split(",") if d and d != "1")
             in stacks and opcode in ("copy", "concatenate", "transpose")]
    assert not moved, moved

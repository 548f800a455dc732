"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) accepts block shapes and stores
that the TPU compiler refuses, so these tests compile each kernel for a
*described* v5e chip — no chip attached — and check that the Mosaic kernel
is in the compiled program. The topology is described only inside a
fixture (a process that loads the TPU library keeps it until it exits), so
every pytest-xdist worker collects the same tests.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import mla_decode
from repro.kernels import moe_gmm
from repro.kernels import node_score as ns


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("U,N", [(8, 8), (128, 16384), (1024, 16384)])
def test_select_best_columns_compiles(one_chip, U, N):
    """The router's (8, 8) bucket, the (128, 16384) bucket that a chunk of
    a 10^4-node step reaches, and metro-10k's step of 1024 rows over 10^4
    nodes, scored from node columns in one launch."""
    spec = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    text = _compiled_text(
        ns.select_best_columns,
        spec((7, N), jnp.float32), spec((4, N), jnp.int32),
        spec((U, 2), jnp.float32), spec((U, 4), jnp.int32),
        spec((8,), jnp.float32))
    assert "tpu_custom_call" in text


def test_select_best_joint_compiles(one_chip):
    text = _compiled_text(
        ns.select_best_joint,
        jax.ShapeDtypeStruct((16, 32, 1024, 8), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((8,), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in text


def test_node_scores_batched_compiles(one_chip):
    text = _compiled_text(
        ns.node_scores_batched,
        jax.ShapeDtypeStruct((128, 16384, 8), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((8,), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in text


# Qwen3-1.7B attention widths: 16 query heads, 8 KV heads, head_dim 128.
@pytest.mark.parametrize("kernel", ["flash", "decode"])
def test_attention_kernels_compile_at_qwen3_widths(one_chip, kernel):
    B, H, K, hd = 4, 16, 8, 128

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if kernel == "flash":
        S = 1024
        text = _compiled_text(fa.flash_attention, sds(B, H, S, hd),
                              sds(B, K, S, hd), sds(B, K, S, hd))
    else:
        S = 2048
        text = _compiled_text(dec.decode_attention, sds(B, H, hd),
                              sds(B, S, K, hd), sds(B, S, K, hd),
                              sds(dtype=jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("window", [None, 512])
def test_decode_attention_compiles_on_the_stacked_cache(one_chip, window):
    """Qwen3-1.7B's decode step: the (28, 8, 1280, 8, 128) K and V stacks
    read at a layer index, with and without a sliding window; nothing but
    the kernel touches the stacks (their (rows, bk * K, hd) view is a
    bitcast)."""
    L, B, S, H, K, hd = 28, 8, 1280, 16, 8, 128

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = functools.partial(dec.decode_attention, window=window)
    text = _compiled_text(fn, sds(B, H, hd), sds(L, B, S, K, hd),
                          sds(L, B, S, K, hd), sds(dtype=jnp.int32),
                          sds(dtype=jnp.int32))
    assert "tpu_custom_call" in text
    assert not [i for i in _instructions(text)
                if i[1] in ((L, B, S, K, hd), (L, B, S * K, hd))
                and i[3] not in ("parameter", "bitcast")]


# DeepSeek-V2-Lite widths: 16 heads of 192 (q, k) and 128 (v) in prefill;
# a 512 + 64 latent per position in decode; experts of 2048 x 1408, 16
# held, rows in the tile layouts of decode (64 x 6 rows) and of one
# prefill pass (16 x 1024 x 6 rows).
def test_flash_attention_compiles_at_mla_widths(one_chip):
    B, H, S = 2, 16, 1024

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    text = _compiled_text(fa.flash_attention, sds(B, H, S, 192),
                          sds(B, H, S, 192), sds(B, H, S, 128))
    assert "tpu_custom_call" in text


def test_mla_decode_attention_compiles(one_chip):
    B, H, S = 64, 16, 1280
    fn = functools.partial(mla_decode.mla_decode_attention,
                           scale=192 ** -0.5, rank=512)
    text = _compiled_text(
        fn, jax.ShapeDtypeStruct((B, H, 576), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((B, S, 576), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,tm", [(64 * 6, 16), (16 * 1024 * 6, 512)])
def test_moe_gmm_compiles(one_chip, rows, tm):
    E, D, F = 16, 2048, 1408
    M = -(-(rows + E * (tm - 1)) // tm) * tm

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = functools.partial(moe_gmm.moe_gmm, tm=tm)
    text = _compiled_text(fn, sds((M, D)), sds((26, E, D, F)),
                          sds((26, E, D, F)), sds((26, E, F, D)),
                          sds((M // tm,), jnp.int32), sds((1,), jnp.int32),
                          sds((1,), jnp.int32))
    assert "tpu_custom_call" in text


def _instructions(text):
    """(name, shape, memory space, opcode, root opcode of a fusion) of each
    bf16 instruction a compiled program materialises: those inside a
    fusion's own computation are left out."""
    comps, comp = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            comps[comp] = []
        elif comp is not None and " = " in line:
            comps[comp].append(line)
    fused = {m.group(1) for m in re.finditer(r" fusion\(.*?calls=%([\w.-]+)",
                                             text)}
    roots = {c: re.search(r"\} ([\w-]+)\(", ls[-1]) for c, ls in comps.items()}
    pat = re.compile(r"%([\w.-]+) = bf16\[([\d,]*)\]\{[^}]*?(S\(\d\))?\} "
                     r"([\w-]+)\(")
    for c, lines in comps.items():
        if c in fused:
            continue
        for line in lines:
            m = pat.search(line)
            if m is None:
                continue
            calls = re.search(r"calls=%([\w.-]+)", line)
            root = roots.get(calls.group(1)) if calls else None
            yield (m.group(1),
                   tuple(int(d) for d in m.group(2).split(",") if d),
                   m.group(3), m.group(4), root and root.group(1))


def _serving_engine(monkeypatch, arch):
    """``ServingEngine`` with the Pallas paths on, at full widths and a few
    layers: Qwen3-1.7B at 4 layers and batch 8, DeepSeek-V2-Lite with 16
    held experts, its prefix and 2 pattern layers at batch 64 (prefilled in
    4 row chunks); cache 1280. Returns the engine, the config and the
    batch."""
    import dataclasses

    from repro.configs import deepseek_v2_lite
    from repro.configs.registry import get_config
    from repro.kernels import ops
    from repro.runtime.serving import ServingEngine

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    if arch == "qwen3-1.7b":
        cfg, B = dataclasses.replace(get_config(arch), num_layers=4,
                                     repeats=0), 8
    else:
        cfg, B = dataclasses.replace(
            deepseek_v2_lite.make_config(experts_held=16), num_layers=3,
            repeats=0), 64
    return ServingEngine(cfg, None, None, max_len=1280, batch_size=B), cfg, B


def _lowered(one_chip, eng, cfg, B):
    """(compiled prefill, compiled decode, abstract cache) on one chip."""
    from repro.models import transformer

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = sds(transformer.abstract_params(cfg))
    cache = sds(transformer.abstract_cache(cfg, B, eng.max_len))
    prefill = eng._prefill.lower(params, {"tokens": jax.ShapeDtypeStruct(
        (B, 1024), jnp.int32, sharding=one_chip)}).compile()
    decode = eng._decode.lower(
        params, cache,
        jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    return prefill, decode, cache


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-v2-lite"])
def test_serving_decode_updates_the_cache_in_place(one_chip, monkeypatch,
                                                   arch):
    """``ServingEngine``'s own decode jit (the cache donated): the whole
    cache is aliased to the output, the program needs less scratch than one
    layer's cache, and nothing makes an array of the stacked cache's or one
    layer's shape (or of a K/V stack's or layer's (S * K, hd) view), in HBM
    or in VMEM, but an in-place ``dynamic-update-slice``:
    ``decode_attention`` reads the K/V stacks where they lie."""
    eng, cfg, B = _serving_engine(monkeypatch, arch)
    _, compiled, cache = _lowered(one_chip, eng, cfg, B)
    mem = compiled.memory_analysis()
    nbytes = lambda x: int(np.prod(x.shape)) * x.dtype.itemsize  # noqa: E731
    assert mem.alias_size_in_bytes >= sum(map(nbytes, jax.tree.leaves(cache)))
    one_layer = jax.tree.leaves(cache["pattern"]["0"])
    assert mem.temp_size_in_bytes < sum(nbytes(x) // x.shape[0]
                                        for x in one_layer)
    stacks = {x.shape for x in jax.tree.leaves(cache) if x.ndim >= 4}
    stacks |= {(n, b, t * k, d) for n, b, t, k, d in
               (s for s in stacks if len(s) == 5)}
    layer = {s[1:] for s in stacks}
    shapes = stacks | layer | {(1,) + s for s in layer} | {
        (b, k, t, d) for b, t, k, d in (s for s in layer if len(s) == 4)}
    bad = [(name, shape, space, op)
           for name, shape, space, op, root in _instructions(compiled.as_text())
           if shape in shapes and op in ("copy", "fusion")
           and not (space is None and root == "dynamic-update-slice")]
    assert not bad, bad


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "deepseek-v2-lite"])
def test_serving_prefill_builds_the_cache_in_decodes_layout(
        one_chip, monkeypatch, arch):
    """The engine's prefill returns the cache in the layouts its decode
    takes, and copies no array of the stacked cache's shape to get there
    (DeepSeek's batch of 64 is prefilled in row chunks through a loop)."""
    eng, cfg, B = _serving_engine(monkeypatch, arch)
    prefill, decode, cache = _lowered(one_chip, eng, cfg, B)
    made = [f.layout for f in jax.tree.leaves(prefill.output_formats[0])]
    taken = [f.layout for f in jax.tree.leaves(decode.input_formats[0][1])]
    assert made == taken
    stacks = {x.shape for x in jax.tree.leaves(cache) if x.ndim >= 4}
    bad = [(name, shape, op)
           for name, shape, _, op, _ in _instructions(prefill.as_text())
           if shape in stacks and op == "copy"]
    assert not bad, bad

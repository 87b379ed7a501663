"""The main path's kernels compile for a TPU v5e — without the chip.

The TPU compiler is installed here and compiles for a chip that is
described and not attached (`jax.experimental.topologies`). Interpret
mode cannot show what these show: the k-side-masked flash kernel passed
every interpret-mode test while the installed compiler never finished it
(a sublane->lane relayout of the mask column, ISSUE 23), and GSPMD
refuses to partition a Mosaic kernel over a mesh. Nothing runs, so
these say nothing about results or times.

Rules this file keeps (on-chip-measurement guide, section 2): the
topology is described inside a module-scoped fixture, never at import
and never in conftest.py (one process at a time may load libtpu, and
every xdist worker imports every test file); compiles happen in the
test's own process; JAX's persistent compilation cache is off around
them (an entry written without a chip cannot be read back and warns);
every compile has a time limit of its own, so a spinning compiler fails
one test instead of eating the suite's clock.
"""
import contextlib
import faulthandler
import importlib.util
import os
import sys
import threading

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from paddle_tpu.ops import flash_attention as fa
from paddle_tpu import profiler as prof

# Each kernel compiles in about a second here; the whole file in ~30 s.
COMPILE_LIMIT_S = 120


@contextlib.contextmanager
def time_limit(what, seconds=COMPILE_LIMIT_S):
    """End this process when the block runs past its limit: the compiler
    spins inside one C++ call that nothing can interrupt (it does release
    the GIL, so a Python timer thread gets to run). Under xdist that is
    one failed test ("worker crashed") and a fresh worker for the rest;
    conftest.py sees to it that the test is not tried again."""
    def give_up():
        print(f"{what}: still running after {seconds} s", file=sys.stderr)
        faulthandler.dump_traceback(file=sys.stderr)
        os._exit(1)

    timer = threading.Timer(seconds, give_up)
    timer.daemon = True
    timer.start()
    try:
        yield
    finally:
        timer.cancel()


@pytest.fixture(scope="module")
def topo():
    """The described chip. Skips where no libtpu is installed, and for
    nothing else: whatever else goes wrong here is an error of every
    test in the file, because a skip would take the only guard that
    these kernels compile away without a sound.

    libtpu admits one process per machine and holds /tmp/libtpu_lockfile
    for that process's life. The rule is about the chip, and nothing
    here touches one — but every xdist worker that is handed a test of
    this file loads libtpu, and all but the first used to be turned away
    ("Internal error when accessing libtpu multi-process lockfile").
    ALLOW_MULTIPLE_LIBTPU_LOAD is libtpu's own switch for that; it is
    read once, when the library loads, so it is set for that moment
    only and the tests' subprocesses do not inherit it."""
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no v5e can be described")
    from jax.experimental import topologies
    env = pytest.MonkeyPatch()
    env.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        with time_limit("describing v5e:2x2"):
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    finally:
        env.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def compiled_text(fn, *args):
    with time_limit("compile"):
        return jax.jit(fn).lower(*args).compile().as_text()


# (shape [b, s, h, d], causal, masked, operand dtype)
KERNEL_CASES = [
    pytest.param((8, 1024, 16, 64), True, False, jnp.bfloat16,
                 id="gpt345m-causal-bf16"),
    pytest.param((32, 512, 12, 64), False, True, jnp.bfloat16,
                 id="bert_base-kvmask-bf16"),
    pytest.param((2, 1024, 4, 64), True, False, jnp.float32,
                 id="fp32-operands"),
    pytest.param((2, 1024, 4, 80), True, False, jnp.bfloat16,
                 id="gpt2p6b-head_dim80"),
    pytest.param((2, 128, 4, 64), True, False, jnp.bfloat16,
                 id="tile128-one-block"),
    pytest.param((2, 256, 4, 64), False, True, jnp.bfloat16,
                 id="tile256-kvmask"),
    pytest.param((2, 384, 4, 64), True, True, jnp.bfloat16,
                 id="tile128-three-blocks-causal-kvmask"),
    # one case on each side of every boundary of `_plan` (PR 28)
    pytest.param((16, 512, 16, 64), False, True, jnp.bfloat16,
                 id="bert_large-kvmask-one-group"),
    pytest.param((2, 2048, 4, 64), True, False, jnp.bfloat16,
                 id="seq2048-last-whole-sequence-block"),
    pytest.param((2, 2176, 4, 64), True, False, jnp.bfloat16,
                 id="seq2176-first-off-the-whole-sequence-block"),
    pytest.param((2, 4096, 4, 64), True, False, jnp.bfloat16,
                 id="seq4096-four-blocks-causal"),
    pytest.param((2, 4096, 4, 64), False, True, jnp.bfloat16,
                 id="seq4096-four-blocks-kvmask"),
    pytest.param((2, 2048, 4, 64), True, False, jnp.float32,
                 id="fp32-operands-four-blocks"),
    pytest.param((2, 1152, 4, 64), True, False, jnp.bfloat16,
                 id="seq1152-three-blocks-of-one-chunk"),
    pytest.param((2, 1280, 4, 64), True, False, jnp.bfloat16,
                 id="seq1280-first-block-of-two-chunks"),
    pytest.param((2, 2048, 4, 64), False, True, jnp.bfloat16,
                 id="seq2048-two-chunks-kvmask"),
    pytest.param((2, 1024, 4, 256), True, False, jnp.bfloat16,
                 id="head_dim256-last-whole-sequence-block"),
]


@pytest.mark.parametrize("shape,causal,masked,dtype", KERNEL_CASES)
def test_flash_kernels_compile(one_chip, shape, causal, masked, dtype):
    """fwd, dq and dk/dv kernels through jax.grad(flash_attention)."""
    assert fa._interpret() is False      # the real kernels, not the
    b, s, _, _ = shape                   # interpreter
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = [x, x, x]
    if masked:
        args.append(jax.ShapeDtypeStruct((b, s), jnp.bool_,
                                         sharding=one_chip))

    def loss_and_grads(q, k, v, m=None):
        return jax.value_and_grad(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=causal, kv_mask=m
            ).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    text = compiled_text(loss_and_grads, *args)
    assert text.count("tpu_custom_call") == 3, text.count("tpu_custom_call")


def test_selected_grouped_kernels_compile_at_keye_widths(one_chip):
    """The Keye cell's attention at its real size: 32 query heads over 4
    key/value heads of 128, 8192 positions, an int8 selection a row."""
    b, s, h, h_kv, d = 2, 8192, 32, 4, 128

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss_and_grads(q, k, v, sel):
        return jax.value_and_grad(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, selection=sel
            ).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    text = compiled_text(loss_and_grads, shape(b, s, h, d),
                         shape(b, s, h_kv, d), shape(b, s, h_kv, d),
                         shape(b, s, s, dtype=jnp.int8))
    # the forward and ONE backward (ISSUE 35): dq comes out of the dk/dv
    # walk, which the compiler takes within the `vmem_limit_bytes` that
    # `_fused_bwd` reckons from these shapes (over it, it refuses)
    assert text.count("tpu_custom_call") == 2
    for name in ("flash_sel_fwd", "flash_sel_bwd_dkv"):
        assert name in text, name
    assert "flash_sel_bwd_dq" not in text
    acc, limit = fa._fused_bwd_vmem(s, 1024, [d], d, selected=True)
    assert acc == s * d * 4 and acc + (18 << 20) < limit < (32 << 20)


@pytest.mark.parametrize("window", [2048, None], ids=["sliding", "full"])
def test_grouped_kernels_compile_at_trinity_widths(one_chip, window):
    """The Trinity cell's attention at its real size: 32 query heads over 4
    key/value heads of 128, 8192 positions, causal, a sliding layer's
    window of 2048 (the grid's reduce axis the 3 key blocks its band
    meets) and a full layer's causal kernels."""
    b, s, h, h_kv, d = 2, 8192, 32, 4, 128
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, h_kv, d), jnp.bfloat16,
                              sharding=one_chip)

    def loss_and_grads(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, window=window
            ).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    text = compiled_text(loss_and_grads, q, kv, kv)
    assert text.count("tpu_custom_call") == 3
    names = prof.WIN_KERNELS if window else prof.KERNELS
    for name in names:
        assert name in text, name
    assert ("flash_win" in text) == bool(window)


@pytest.mark.parametrize("concat", [False, True],
                         ids=["rotary-key-by-index-map", "key-concatenated"])
def test_latent_kernels_compile_at_kanana_widths(one_chip, concat):
    """The kanana cell's attention at its real size: 32 heads, scores over
    192 (128 + one shared rotary head of 64), values of 128, 8192
    positions; both ways of handing the kernels the rotary key. As built,
    the kernels read the projections' own arrays: q without position
    `[b, s, h 128]` and kv `[b, s, h 256]` a 128- and a 256-lane block a
    head, strided along lanes."""
    b, s, h, dn, dr, dv = 2, 8192, 32, 128, 64, 128

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    def one_part(q, kn, kr, v):
        # the other way of handing the kernels the rotary key: the key
        # concatenated beforehand to one part of 192, [b h, s, w] operands
        def to3(x):
            return jnp.swapaxes(x, 1, 2).reshape(-1, s, x.shape[-1])
        k = jnp.concatenate([kn, jnp.broadcast_to(kr, (b, s, h, dr))], -1)
        one = fa.Lay()
        parts = fa.Parts(q_lays=(one,), k_lays=(one, one),
                         q=((0, 0, dn + dr),), k=((0, 0, dn + dr),),
                         v=(1, 0, dv))
        return fa._mla3((to3(q),), (to3(k), to3(v)), parts,
                        (dn + dr) ** -0.5)

    def loss_and_grads(*args):
        latent = one_part if concat else fa.flash_attention_latent
        return jax.value_and_grad(
            lambda *a: latent(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3))(*args)

    if concat:
        args = (shape(b, s, h, dn + dr), shape(b, s, h, dn),
                shape(b, s, 1, dr), shape(b, s, h, dv))
    else:
        args = (shape(b, s, h, dn), shape(b, h, s, dr),
                shape(b, s, h, dn + dv), shape(b, s, 1, dr))
    text = compiled_text(loss_and_grads, *args)
    assert text.count("tpu_custom_call") == 2
    for name in ("flash_mla_fwd", "flash_mla_bwd_dkv"):
        assert name in text, name
    assert "flash_mla_bwd_dq" not in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_indexer_compiles_at_keye_widths(one_chip, dtype):
    """The index-score kernel (16 heads of 64 over 8192 positions) and
    the exact top-2048 of all its rows, one kernel too."""
    from paddle_tpu.ops import index_select as ix

    def shape(*dims, dtype=dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    text = compiled_text(ix.index_scores, shape(2, 8192, 16, 64),
                         shape(2, 8192, 64),
                         shape(2, 8192, 16, dtype=jnp.float32))
    assert "%index_scores" in text
    text = compiled_text(lambda x: ix.index_topk(x, 2048),
                         shape(2, 8192, 8192, dtype=jnp.float32))
    assert "%index_topk" in text and text.count("tpu_custom_call") == 1


def test_dispatch_shards_kernel_over_four_chips(topo, monkeypatch):
    """scaled_dot_product_attention under a dp2 x mp2 mesh at the
    GPT-345M shape: the kernel must arrive wrapped in shard_map, or the
    compiler refuses it ("Mosaic kernels cannot be automatically
    partitioned"). The dispatch asks which backend is the default — the
    CPU, in this process — so the test answers for it."""
    from paddle_tpu.distributed import build_mesh, topology
    from paddle_tpu.nn.functional import attention as A
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(topology, "_GLOBAL_MESH", None)
    mesh = build_mesh(dp=2, mp=2, devices=list(topo.devices))
    sh = NamedSharding(mesh, P("data", None, "model", None))
    x = jax.ShapeDtypeStruct((8, 1024, 16, 64), jnp.bfloat16, sharding=sh)

    def loss_and_grads(q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: A.scaled_dot_product_attention(
                q, k, v, is_causal=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    text = compiled_text(loss_and_grads, x, x, x)
    assert text.count("tpu_custom_call") == 3


def test_dispatch_shards_latent_kernels_over_four_chips(topo, monkeypatch):
    """`F.latent_attention` under a dp2 x mp2 mesh at the kanana widths:
    the two `flash_mla_*` kernels per shard (16 heads a chip, the one
    rotary head whole on each), not the XLA path."""
    from paddle_tpu.distributed import build_mesh, topology
    from paddle_tpu.nn.functional import attention as A
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(topology, "_GLOBAL_MESH", None)
    mesh = build_mesh(dp=2, mp=2, devices=list(topo.devices))

    def shape(*dims, spec=P("data", None, "model", None)):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16,
                                    sharding=NamedSharding(mesh, spec))

    def loss_and_grads(*args):
        return jax.value_and_grad(
            lambda *a: A.latent_attention(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3))(*args)

    # the projections' arrays: q without position, the rotary query part
    # head-major, kv whole, the one rotary key head
    text = compiled_text(loss_and_grads, shape(2, 8192, 32, 128),
                         shape(2, 32, 8192, 64, spec=P("data", "model")),
                         shape(2, 8192, 32, 256),
                         shape(2, 8192, 1, 64, spec=P("data")))
    assert text.count("tpu_custom_call") == 2
    for name in ("flash_mla_fwd", "flash_mla_bwd_dkv"):
        assert name in text, name
    assert "flash_mla_bwd_dq" not in text


def test_dispatch_keeps_one_chip_under_a_stale_mesh(topo, one_chip,
                                                    monkeypatch):
    """A step traced for one device (`mesh_scope`) while a four-device
    mesh from an earlier step is still the global one: the kernel must
    compile for the one chip, unwrapped."""
    from paddle_tpu.distributed import build_mesh, topology
    from paddle_tpu.nn.functional import attention as A
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(topology, "_GLOBAL_MESH", None)
    one = build_mesh(devices=list(topo.devices)[:1])
    build_mesh(dp=2, mp=2, devices=list(topo.devices))   # the stale one
    x = jax.ShapeDtypeStruct((8, 1024, 16, 64), jnp.bfloat16,
                             sharding=one_chip)

    def loss_and_grads(q, k, v):
        with topology.mesh_scope(one):
            return jax.value_and_grad(
                lambda q, k, v: A.scaled_dot_product_attention(
                    q, k, v, is_causal=True).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v)

    assert "shard_map" not in str(jax.make_jaxpr(loss_and_grads)(x, x, x))
    text = compiled_text(loss_and_grads, x, x, x)
    assert text.count("tpu_custom_call") == 3


def test_tensor_parallel_sums_compile_to_exchanges(topo, monkeypatch):
    """A fused head projection into a row-parallel one on the 2 x 2 mesh
    (ZeRO x TP), forward and backward: with two chips on 'model' the row
    product's sum and the column product's backward sum each arrive as
    ONE asynchronous collective-permute of the activation, nothing of
    activation size is all-reduced or gathered, and the partly-manual
    `shard_map` they sit in compiles beside GSPMD's own partitioning."""
    import re
    from paddle_tpu.distributed import build_mesh, topology
    from paddle_tpu.distributed.meta_parallel import (ColumnParallelLinear,
                                                      RowParallelLinear)
    from paddle_tpu.nn.layer import Layer, functional_call, trainable_state
    monkeypatch.setattr(topology, "_GLOBAL_MESH", None)
    mesh = build_mesh(sharding=2, mp=2, devices=list(topo.devices))
    rows, seq, width, heads = 16, 1024, 1024, 16

    class Attention(Layer):
        def __init__(self):
            super().__init__()
            self.qkv = ColumnParallelLinear(width, 3 * width,
                                            gather_output=False,
                                            compute_dtype=jnp.bfloat16)
            self.out = RowParallelLinear(width, width,
                                         input_is_parallel=True,
                                         compute_dtype=jnp.bfloat16)

        def forward(self, x):
            q = self.qkv.project_heads(x, 3, heads)[:, :, 0]
            return self.out(q.reshape(x.shape))

    layer = Attention()
    params = {
        n: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=NamedSharding(
            mesh, dict(layer.named_parameters())[n].sharding_spec or P()))
        for n, v in trainable_state(layer).items()}
    x = jax.ShapeDtypeStruct(
        (rows, seq, width), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(("data", "sharding"), None, None)))

    def loss_and_grads(p, x):
        def loss(p, x):
            with topology.mesh_scope(mesh):
                return functional_call(layer, p, x)[0].astype(
                    jnp.float32).sum()
        return jax.value_and_grad(loss, argnums=(0, 1))(p, x)

    text = compiled_text(loss_and_grads, params, x)
    half = f"bf16[{rows // 2},{seq},{width}]"
    moved = re.findall(r"= \(?(\S+?)[,)] .*?(all-reduce|all-gather|all-to-all"
                       r"|collective-permute-start)\(", text)
    assert [op for shape, op in moved if shape.startswith(half)] == \
        ["collective-permute-start"] * 2, moved

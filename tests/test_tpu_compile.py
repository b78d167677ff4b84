"""Compile rehearsal: the main-path Pallas kernels, field.matmul's choice of
kernel at the protocol's product shapes, and the setup and loop programs at
the paper's full CIFAR-10 size, compile for a TPU v5e.

Interpret mode (what the other kernel tests run on the CPU) accepts code
that Mosaic refuses -- unaligned blocks, vector loads of per-client
scalars, more VMEM than a kernel may use.  These tests compile each kernel
for a described v5e chip (no chip attached) at the shapes the chip smoke
run and the registered workloads use, with interpret mode off, and check
that the compiled program holds the Mosaic kernel (`tpu_custom_call`).
The whole-program test checks the compiler's memory analysis against one
chip's memory instead.  Nothing runs, so they say nothing about results
or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.core import field
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def mosaic(monkeypatch):
    """Compile the kernels (interpret off) and let field.matmul choose as on
    a TPU, although the backend is the CPU, with the persistent compilation
    cache off: entries written for a described chip cannot be read back
    without one."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    monkeypatch.setattr(field, "_tpu_backend", lambda: True)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


_KW = dict(q_eta=3, inv2k1=12345, k1=19, force_pallas=True)


@pytest.mark.parametrize("n,m,d,c", [
    (50, 8, 3073, 1),      # chip smoke: cifar10_case1 at m=128, K=16
    (50, 564, 3073, 1),    # cifar10_case1 at the paper's m=9019
    (50, 375, 5000, 1),    # gisette_case1: wide d shrinks bm
    (13, 98, 24, 10),      # mnist10_like: class-batched (d, C)
])
def test_fused_step_compiles(one_chip, n, m, d, c):
    txt = _compile_text(
        lambda *a: ops.fused_step(*a, **_KW), one_chip,
        (n, m, d), (n, d, c), (2,), (n,), (n,), (n,),
        *[(n, d, c)] * 5)
    assert "tpu_custom_call" in txt


def test_coded_gradient_batched_compiles(one_chip):
    txt = _compile_text(
        lambda x, w, cf: ops.coded_gradient_batched(x, w, cf,
                                                    force_pallas=True),
        one_chip, (50, 16, 3073), (50, 3073), (2,))
    assert "tpu_custom_call" in txt


def test_coded_gradient_matrix_compiles(one_chip):
    txt = _compile_text(
        lambda x, w, cf: ops.coded_gradient_matrix(x, w, cf,
                                                   force_pallas=True),
        one_chip, (13, 98, 24), (13, 24, 10), (2,))
    assert "tpu_custom_call" in txt


def test_coded_gradient_single_client_compiles(one_chip):
    txt = _compile_text(
        lambda x, w, cf: ops.coded_gradient(x, w, cf, force_pallas=True),
        one_chip, (256, 3073), (3073,), (2,))
    assert "tpu_custom_call" in txt


def test_modmatmul_compiles(one_chip):
    txt = _compile_text(
        lambda a, b: ops.modmatmul(a, b, force_pallas=True),
        one_chip, (512, 512), (512, 512))
    assert "tpu_custom_call" in txt


def test_poly_eval_compiles(one_chip):
    txt = _compile_text(
        lambda z, cf: ops.poly_eval(z, cf, force_pallas=True),
        one_chip, (50, 3073), (2,))
    assert "tpu_custom_call" in txt


# field.matmul's products on the cells' paths (per holder; Case 2 at full m
# unless named) and whether each takes the short-contraction kernel: the
# Mosaic call where the contraction is short and the output wide, the jnp
# limb path elsewhere.  `batch` holders under vmap, `a` unbatched (None) or
# batched.
@pytest.mark.parametrize("a_shape,b_shape,batch,kernel", [
    ((50, 17), (17, 24584), (50, None), True),    # setup lcc_encode
    ((50, 7), (7, 245840), None, True),           # setup share_with, X
    ((50, 7), (7, 172088), None, True),           # setup share_with, Z
    ((50, 1), (1, 393344), None, True),           # X sharing, Case 1
    ((1, 8), (8, 1229200), None, True),           # setup reconstruct
    ((50, 17), (17, 3073), (50, None), True),     # loop encode_model
    ((1, 50), (50, 153650), None, True),          # reconstruct, all holders
    ((50, 7), (7, 153650), None, True),           # step_rand share of zeros
    ((1, 50), (50, 3073), (50, None), True),      # step_rand base
    ((50, 7), (7, 3073), None, True),             # TruncPr shares
    ((3073, 80), (80, 1), (50, 0), False),        # setup X^T y local product
    ((902, 3073), (3073, 1), (50, 0), False),     # ref.fused_step, X~ w
    ((3073, 902), (902, 1), (50, 0), False),      # ref.fused_step, X~^T g
], ids=["lcc_encode", "share_x", "share_z", "share_x_case1", "reconstruct",
        "encode_model", "reconstruct_all", "mix", "base", "trunc_pr",
        "xty_local_product", "fused_step_xw", "fused_step_xtg"])
def test_field_matmul_chooses_by_shape(one_chip, a_shape, b_shape, batch,
                                       kernel):
    # a fresh function: no trace cached under the CPU's choice is reused
    fn, shapes = (lambda a, b: field.matmul(a, b)), [a_shape, b_shape]
    if batch is not None:
        size, a_axis = batch
        fn = jax.vmap(fn, in_axes=(a_axis, 0))
        shapes = [a_shape if a_axis is None else (size,) + a_shape,
                  (size,) + b_shape]
    txt = _compile_text(fn, one_chip, *shapes)
    assert ("tpu_custom_call" in txt) == kernel


# The paper's CIFAR-10 Case 2 at full m on one 16 GB v5e.  Setup's compiled
# temporaries and the X~ it leaves behind take at most SETUP_BUDGET; the
# loop program, which runs after setup's temporaries are freed, holds X~
# among its arguments and outputs and must fit in what remains.
CHIP_BYTES = 16 * 2**30
SETUP_BUDGET = 8 * 2**30


def test_full_size_case2_setup_and_loop_fit_one_chip(one_chip):
    from repro.core import objectives, protocol
    n, k, t, m, d = 50, 10, 7, 9019, 3073
    cfg = protocol.CopmlConfig(n_clients=n, k=k, t=t)
    mk = -(-m // k)
    assert protocol.setup_chunks(n, mk, d) > 1

    def shape(s, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)

    key = shape((2,), jnp.uint32)
    setup = protocol._setup_program.lower(
        cfg, objectives.BINARY_LOGISTIC, m, d, key,
        shape((m, d), jnp.float32), shape((m,), jnp.float32)).compile()
    assert "tpu_custom_call" in setup.as_text()    # the short products
    xtilde = n * mk * d * 4
    temp = setup.memory_analysis().temp_size_in_bytes
    assert temp + xtilde <= SETUP_BUDGET, (temp, xtilde)

    state = protocol.CopmlState(
        w_shares=shape((n, d)), coded_x=shape((n, mk, d)),
        xty_shares=shape((n, d)), step=shape(()))
    loop = protocol._scan_iterations.lower(
        protocol.Copml(cfg, m, d), key, state, 50, None, False,
        None).compile().memory_analysis()
    held = loop.temp_size_in_bytes + loop.argument_size_in_bytes + \
        loop.output_size_in_bytes
    assert held <= CHIP_BYTES - SETUP_BUDGET, held

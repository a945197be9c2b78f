"""The inverse of I + A of the Gated DeltaNet scan
(``ops/gated_delta._unit_lower_inverse``) where a Neumann series would not
survive, on both of its schedules: XLA's loop, and the Mosaic kernel
(``ops/pallas_kernels.unit_lower_inverse_slabs``) in interpret mode, chosen
through the function that chooses with its one question about the platform
answered by a fixture; counts of matrices that are no whole blocks, smaller
chunks, the cotangent rule through each, what the chooser answers.  Split
from tests/test_gated_delta.py (which borrows the fixtures) so that neither
file is a worker's whole share of the run under --dist loadfile.  CPU
only."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from horovod_tpu.ops import gated_delta as gd  # noqa: E402


def jit(fn):
    """A jit of its own: ``jax.jit`` of one function object is one cache
    whatever the chooser answered when it was first traced."""
    return jax.jit(lambda *args: fn(*args))


@pytest.fixture
def choose(monkeypatch):
    """``choose(on_tpu)`` answers the chooser's one question about the
    platform.  In a process the answer never changes, and JAX keeps what
    it traced (a ``custom_vjp``'s body too, by its shapes): the caches go
    with every change of it."""
    def choose(on_tpu: bool):
        monkeypatch.setattr(gd, "_on_tpu", lambda: on_tpu)
        jax.clear_caches()

    yield choose
    jax.clear_caches()


@pytest.fixture(params=["loop", "kernel"])
def schedule(request, choose):
    """Which schedule ``_unit_lower_inverse`` chooses: off the TPU XLA's
    loop; with the platform answered as a TPU the kernel, which itself
    still follows ``pallas_kernels._use_interpret()`` and so runs in the
    interpreter here."""
    choose(request.param == "kernel")
    return request.param


@pytest.mark.parametrize("case", ["random", "equal_keys"])
def test_the_inverse_of_i_plus_a(case, schedule):
    """Against numpy's inverse in float64.  ``equal_keys``: a = the strict
    lower triangle of ones (equal unit keys, beta 1, no decay), whose
    powers reach 1e18 before they cancel; forward substitution gives the
    bidiagonal inverse exactly."""
    c = gd.CHUNK
    assert gd._inverse_on_kernel(c) == (schedule == "kernel")
    if case == "random":
        a = np.tril(np.random.default_rng(0).normal(size=(3, c, c)) * 0.3,
                    -1)
    else:
        a = np.tril(np.ones((1, c, c)), -1)
    a32 = jnp.asarray(a, jnp.float32)
    assert ("pallas_call" in str(jax.make_jaxpr(gd._unit_lower_inverse)(a32))
            ) == (schedule == "kernel")
    got = jit(gd._unit_lower_inverse)(a32)
    want = np.linalg.inv(np.eye(c) + a)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-5 * np.abs(want).max())
    # its cotangent rule against differentiating the substitution itself
    # (XLA's loop: an interpreted kernel has no derivative), on the
    # leading 16 x 16 (the rule reads no size)
    c = 16
    a = a[:, :c, :c]
    ct = jnp.asarray(np.random.default_rng(1).normal(size=a.shape),
                     jnp.float32)
    a32 = jnp.asarray(a, jnp.float32)
    rule = jax.jit(jax.grad(
        lambda x: jnp.sum(gd._unit_lower_inverse(x) * ct)))(a32)
    plain = jax.jit(jax.grad(lambda x: jnp.sum(jnp.transpose(
        gd._inverse_slabs_loop(jnp.transpose(x, (1, 2, 0))), (2, 0, 1))
        * ct)))(a32)
    mask = np.tril(np.ones((c, c), bool), -1)
    np.testing.assert_allclose(rule, np.where(mask, plain, 0.0), rtol=2e-4,
                               atol=2e-4 * float(jnp.abs(plain).max()))


@pytest.mark.parametrize("count", [1, 130, 256])
def test_the_kernel_takes_any_count_of_matrices(count, choose):
    """A block is 128 matrices: a count that is no multiple of it is
    padded with zeros (whose inverse is I) and cut again; leading
    dimensions are the caller's."""
    choose(True)
    c = gd.CHUNK
    a = np.tril(np.random.default_rng(count).normal(size=(count, c, c))
                * 0.3, -1)
    got = jit(gd._unit_lower_inverse)(
        jnp.asarray(a, jnp.float32).reshape((1, count, 1, c, c)))
    assert got.shape == (1, count, 1, c, c) and got.dtype == jnp.float32
    want = np.linalg.inv(np.eye(c) + a)
    np.testing.assert_allclose(got[0, :, 0], want, rtol=1e-4,
                               atol=1e-5 * np.abs(want).max())


@pytest.mark.parametrize("c", [8, 16, 32])
def test_the_kernel_at_smaller_chunks_is_the_loop(c, choose):
    a = jnp.asarray(np.tril(np.random.default_rng(c).normal(
        size=(5, c, c)) * 0.3, -1), jnp.float32)
    loop = jit(gd._unit_lower_inverse)(a)
    choose(True)
    assert gd._inverse_on_kernel(c)
    np.testing.assert_allclose(jit(gd._unit_lower_inverse)(a),
                               loop, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("on_tpu, c, kernel", [
    (False, 64, False), (True, 64, True), (True, 16, True),
    (True, 12, False), (True, 128, False), (True, 7, False)])
def test_the_chooser_reads_the_platform_and_the_chunk(choose, on_tpu, c,
                                                      kernel):
    """XLA's loop off the TPU and for a C that is not whole groups of 8
    rows or whose blocks would not fit VMEM; the loop then gives the same
    inverse as ever."""
    choose(on_tpu)
    assert gd._inverse_on_kernel(c) is kernel
    if not kernel and c < 64:
        a = np.tril(np.random.default_rng(2).normal(size=(2, c, c)), -1)
        np.testing.assert_allclose(
            jit(gd._unit_lower_inverse)(jnp.asarray(a, jnp.float32)),
            np.linalg.inv(np.eye(c) + a), rtol=1e-4, atol=1e-4)


def test_the_chooser_answers_the_loop_here():
    """Unforced, on the CPU: no test of the model pays the interpreter."""
    assert not gd._on_tpu() and not gd._inverse_on_kernel(gd.CHUNK)

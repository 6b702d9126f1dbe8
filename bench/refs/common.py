"""What the plain references share: the seed's key, and the two matmul
precisions a reference runs in.

``exact`` is float32 at ``highest`` (a TPU otherwise runs float32 dots in
bfloat16 passes).  ``fp8`` is the control: both operands of every matmul
rounded to float8 e4m3 with one scale per tensor (amax mapped to 448, the
format's largest finite value), then multiplied with float32
accumulation.  That is the precision step below the configurations'
bfloat16 that a later change could be tempted to take.
"""
import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = 448.0


def key_from_seed(seed: int):
    """A PRNG key from any whole number (seeds may pass 2**32,
    which ``PRNGKey`` would silently truncate)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def exact(spec, a, b):
    return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)


def _fp8(a):
    a = a.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(F32)
    return q, scale


def fp8(spec, a, b):
    qa, sa = _fp8(a)
    qb, sb = _fp8(b)
    return jnp.einsum(spec, qa, qb, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32) * (sa * sb)


MATMULS = {"exact": exact, "fp8": fp8}


def normal(key, shape, dtype, std):
    """Seeded normal weights made directly in the served type."""
    return (jax.random.normal(key, shape, dtype) * std).astype(dtype)


def uniform(key, shape, dtype, lo, hi):
    return jax.random.uniform(key, shape, F32, lo, hi).astype(dtype)

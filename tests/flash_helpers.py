"""What the flash kernels' test files (``tests/test_flash_*.py``) share."""
import jax
import jax.numpy as jnp


def rand_qkv(rng, shape, dtype=jnp.float32):
    return tuple(jnp.asarray(rng.randn(*shape), dtype) for _ in range(3))


def merge_heads(x):
    b, h, s, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b, s, h * d)


def split_heads(x, h):
    b, s, hd = x.shape
    return jnp.transpose(x.reshape(b, s, h, hd // h), (0, 2, 1, 3))


def dense_band(q, k, v, window):
    """Plain attention under an explicit boolean band mask."""
    s = q.shape[2]
    ahead = jnp.arange(s)[None, :] - jnp.arange(s)[:, None]
    keep = (ahead >= -window[0]) & (ahead <= window[1])
    scores = jnp.einsum('bhqd,bhkd->bhqk', q, k) * q.shape[-1] ** -0.5
    return jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(
        jnp.where(keep, scores, -jnp.inf), axis=-1), v)

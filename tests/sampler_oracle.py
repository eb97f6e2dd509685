"""The serving step's sampling tail as it stood before PR 39, kept as the
tests' oracle (tests/test_sampler_tail.py, tests/test_step_io.py): the
whole chain for every row of every step, two vocabulary-wide sorts and a
categorical draw, whatever the rows ask for."""
import jax
import jax.numpy as jnp

from paddle_infer_tpu.inference import sampling


def process_rows(logits, samp, steps):
    logits = logits.astype(jnp.float32)
    vocab = logits.shape[-1]

    eos = samp["eos"]
    banned = jnp.logical_and(eos >= 0, steps < samp["min_len"])
    eos_col = jax.nn.one_hot(jnp.maximum(eos, 0), vocab, dtype=jnp.bool_)
    logits = jnp.where(jnp.logical_and(banned[:, None], eos_col),
                       sampling.NEG_INF, logits)

    t = jnp.maximum(samp["temperature"].astype(jnp.float32), 1e-6)
    logits = logits / t[:, None]

    k = jnp.where(samp["top_k"] > 0,
                  jnp.clip(samp["top_k"], 1, vocab), vocab)
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    logits = jnp.where(logits < kth, sampling.NEG_INF, logits)

    sorted2 = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted2, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < samp["top_p"][:, None]
    keep = keep.at[..., 0].set(True)
    thresh = jnp.min(jnp.where(keep, sorted2, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where(logits < thresh, sampling.NEG_INF, logits)


def pick_rows(proc, samp, steps, keys):
    step_keys = jax.vmap(jax.random.fold_in)(keys, steps)
    sampled = jax.vmap(
        lambda k, row: jax.random.categorical(k, row))(step_keys, proc)
    greedy = jnp.argmax(proc, axis=-1)
    return jnp.where(samp["do_sample"], sampled, greedy).astype(jnp.int32)

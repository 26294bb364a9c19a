"""What a decode engine must serve, computed without the engine.

The engine's tests used to compare one of its arms with another (slab
against paged, monolithic against chunked), although the arms shared
admission, sampling and harvest code. These references share none of it:
they call the model, and nothing of ``engine/``.

- :func:`teacher_forced`: the model's full forward (``model.apply``) over
  the growing sequence, one token a pass: no cache at all. The reference
  for a full-precision cache. It also draws a sampled row, by the rule the
  engine documents: key = fold_in(fold_in(PRNGKey(base_seed), seed), index
  of the token in the request), top-k mask, temperature, categorical.
- :func:`cached_greedy`: ``model.prefill`` + ``model.decode_step`` on the
  model's own slab ``KVCache`` (its ``kv_dtype``). The reference for a
  quantized cache, whose rounding a cache-free forward does not reproduce.
"""

import jax
import jax.numpy as jnp
import numpy as np


def teacher_forced(model, params, prompt, n, temperature=0.0, top_k=0,
                   seed=0, base_seed=0):
    seq = [int(t) for t in prompt]
    # Right-padded to one width (a causal model's logits at a position do
    # not see what follows it), so the whole loop is one compiled shape.
    width = -(-(len(seq) + n) // 32) * 32
    forward = jax.jit(model.apply)
    out = []
    for idx in range(n):
        tokens = np.zeros((1, width), np.int32)
        tokens[0, :len(seq)] = seq
        mask = (np.arange(width) < len(seq))[None].astype(np.int32)
        logits = forward(params, jnp.asarray(tokens), jnp.asarray(mask))
        row = logits[0, len(seq) - 1].astype(jnp.float32)
        if temperature > 0.0:
            if top_k > 0:
                kth = jnp.sort(row)[-top_k]
                row = jnp.where(row < kth, -jnp.inf, row)
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(base_seed), seed), idx)
            nxt = int(jax.random.categorical(key, row / temperature))
        else:
            nxt = int(jnp.argmax(row))
        out.append(nxt)
        seq.append(nxt)
    return out


def cached_greedy(model, params, prompt, n):
    prompt = np.asarray(prompt, np.int32)
    cache = model.make_cache(1, -(-(int(prompt.size) + n) // 64) * 64)
    width = -(-int(prompt.size) // 32) * 32   # right-padded: one shape
    tokens = np.zeros((1, width), np.int32)
    tokens[0, :prompt.size] = prompt
    mask = (np.arange(width) < prompt.size)[None].astype(np.int32)
    logits, cache = jax.jit(model.prefill)(
        params, jnp.asarray(tokens), jnp.asarray(mask), cache)
    out = [int(jnp.argmax(logits[0].astype(jnp.float32)))]
    step = jax.jit(model.decode_step)
    active = jnp.ones((1,), bool)
    for _ in range(n - 1):
        logits, cache = step(
            params, jnp.asarray([[out[-1]]], jnp.int32), cache, active)
        out.append(int(jnp.argmax(logits[0].astype(jnp.float32))))
    return out


def expected_tokens(model, params, payload, cached=False):
    """The tokens a request's payload must be served (greedy, or the
    seeded sampled row through :func:`teacher_forced`)."""
    n = int(payload["max_new_tokens"])
    if cached:
        return cached_greedy(model, params, payload["tokens"], n)
    return teacher_forced(
        model, params, payload["tokens"], n,
        temperature=float(payload.get("temperature", 0.0)),
        top_k=int(payload.get("top_k", 0)), seed=int(payload.get("seed", 0)),
    )


def assert_served(model, params, reqs, served, cached=False):
    """Every request of ``reqs`` was served the reference's tokens. With
    ``cached`` (a quantized cache) sampled rows are left out: their draw
    sits on logits only the cache-free forward is the reference for."""
    for req, got in zip(reqs, served):
        p = req.payload
        if cached and float(p.get("temperature", 0.0)) > 0.0:
            assert len(got) == int(p["max_new_tokens"])
            continue
        assert list(got) == expected_tokens(model, params, p, cached), \
            f"prompt of {len(p['tokens'])} tokens"

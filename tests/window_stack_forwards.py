"""A window stack's forwards as the step programs run them, for the
families whose window layers keep a per-slot ring beside the pool
(``tests/test_exaone_moe.py``, ``tests/test_mellum.py``): one whole
prompt (or chunks of one), then decode steps through rings and pool,
returned as log-softmax rows to hold against a plain reference."""

import jax
import jax.numpy as jnp
import numpy as np

from vgate_tpu.models import decoder, hybrid
from vgate_tpu.runtime.kv_cache import KVGeometry, make_kv_buffers

# jitted, the spec static
PREFILL = jax.jit(decoder.prefill_forward, static_argnums=1)
SUFFIX = jax.jit(decoder.prefill_suffix_forward, static_argnums=1)
DECODE = jax.jit(decoder.decode_forward, static_argnums=1)


def fresh_cache(spec, page, slots):
    geo = KVGeometry(
        num_layers=spec.attn_layers, num_pages=64, page_size=page,
        kv_heads=spec.num_kv_heads, head_dim=spec.head_dim,
        max_model_len=128, dtype_bytes=4)
    return (*make_kv_buffers(geo, jnp.float32),
            hybrid.make_state(spec, slots, jnp.float32, page))


def served_logprobs(spec, params, seq, prompt_len, *, page, slots, bucket,
                    slot=2, chunks=None):
    """Log-softmax rows for positions ``prompt_len - 1 .. len(seq) - 2``
    from the program's forwards: the prompt whole in ``bucket`` rows (or
    in ``chunks``, each a suffix against what the chunks before left in
    pages and ring), then one decode step a token through ring and
    pool."""
    kp, vp, st = fresh_cache(spec, page, slots)
    table = np.arange(1, 33, dtype=np.int32)[None]
    one = lambda v: jnp.asarray([v])
    if chunks is None:
        S = bucket  # one program whatever the prompt's length
        assert prompt_len <= S
        toks = np.zeros((1, S), np.int32)
        toks[0, :prompt_len] = seq[:prompt_len]
        logits, kp, vp, st = PREFILL(
            params, spec, jnp.asarray(toks), one(prompt_len), kp, vp,
            jnp.asarray(table[:, :S // page]), state=st, slots=one(slot))
    else:
        done = 0
        for want in chunks:
            n = min(want, prompt_len - done)
            S = -(-n // 8) * 8
            toks = np.zeros((1, S), np.int32)
            toks[0, :n] = seq[done:done + n]
            own = table[:, done // page: (done + S) // page]
            logits, kp, vp, st = SUFFIX(
                params, spec, jnp.asarray(toks), one(done), one(n), kp, vp,
                jnp.asarray(own), jnp.asarray(table), state=st,
                slots=one(slot))
            done += n
    rows = [jax.nn.log_softmax(logits[0])]
    tables = np.zeros((slots, 32), np.int32)
    tables[slot] = table[0]
    active = np.arange(slots) == slot
    for pos in range(prompt_len, len(seq) - 1):
        tok = np.where(active, seq[pos], 0).astype(np.int32)
        at = np.where(active, pos, 0).astype(np.int32)
        logits, kp, vp, st, _ = DECODE(
            params, spec, jnp.asarray(tok), jnp.asarray(at), kp, vp,
            jnp.asarray(tables), active=jnp.asarray(active), state=st)
        rows.append(jax.nn.log_softmax(logits[slot]))
    return np.stack([np.asarray(r) for r in rows])

"""The one general traffic generator: a traffic mix is a data file under
``benchmark/traffic/`` that this module turns into inputs from ``--seed``.

The same seed gives the same inputs; every seed gives the same sizes and
arrivals, in the same order (they come from the mix's own
``lengths_seed``), so that the seed changes what is computed and not how
much. A mix names a *law* for each of its parts (lengths, arrivals), looked
up in the tables below; the rest of its file are that law's parameters.
Nothing here imports the program.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed, stream):
    """A key for one stream of one run. ``--seed`` may exceed 32 signed
    bits, so it is folded in as two halves."""
    seed = int(seed)
    key = jax.random.PRNGKey(stream)
    key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def train_batches(seed, traffic, model, dtype):
    """``(xs, ys)`` for a ``prestaged_batches`` mix: ``batches`` batches of
    ``batch`` NHWC images of standard normal pixels and uniform labels,
    made on the device in one jitted call. ``xs[i]`` is one batch."""
    shape = (traffic["batches"], traffic["batch"], model["image"],
             model["image"], 3)

    @jax.jit
    def make(kx, ky):
        xs = jax.random.normal(kx, shape, jnp.float32).astype(dtype)
        ys = jax.random.randint(ky, shape[:2], 0, model["classes"],
                                jnp.int32)
        return xs, ys

    return make(key_of(seed, 1), key_of(seed, 2))


def _clip(lengths, spec):
    return np.clip(lengths, spec["min"], spec["max"]).astype(np.int64)


def _geometric(rng, n, spec):
    """Whole lengths from ``min`` upwards with the mean ``mean``: the
    least-assuming law where a source publishes a mean and nothing else."""
    p = 1.0 / (spec["mean"] - spec["min"] + 1.0)
    return _clip(spec["min"] + rng.geometric(p, n) - 1, spec)


def _lognormal(rng, n, spec):
    return _clip(np.rint(np.exp(math.log(spec["median"])
                                + spec["sigma"] * rng.standard_normal(n))),
                 spec)


def _fixed(rng, n, spec):
    return np.full(n, spec["value"], np.int64)


#: ``{"law": name, ...parameters}`` of ``prompt_tokens``, ``output_tokens``
LENGTH_LAWS = {"geometric": _geometric, "lognormal": _lognormal,
               "fixed": _fixed}


def _closed(rng, seconds, spec):
    """A batch handed to ``clients`` clients, each sending its next
    request when its last is answered: every request is due at once, and
    ``requests_per_second`` times ``--seconds`` of them make a run that
    lasts about ``--seconds`` at the speed the mix was sized at."""
    n = max(spec["clients"], round(spec["requests_per_second"] * seconds))
    return np.zeros(n)


def _open(rng, seconds, spec):
    """Arrivals at ``rate_per_s`` over ``--seconds`` whether or not earlier
    requests were answered. Gaps are gamma with the coefficient of
    variation ``cv``: 1 (the default) is a Poisson process, more is
    burstier."""
    cv2 = float(spec.get("cv", 1.0)) ** 2
    n = int(spec["rate_per_s"] * seconds * 2) + 16
    due = np.cumsum(rng.gamma(1.0 / cv2, cv2 / spec["rate_per_s"], n))
    return due[due < seconds]


#: ``{"law": name, ...parameters}`` of ``arrivals``
ARRIVAL_LAWS = {"closed": _closed, "open": _open}

#: lengths are drawn this many at a time, so that a longer run's requests
#: begin with a shorter run's
DRAWN = 4096


def requests(seed, traffic, vocab_size, seconds):
    """``[(due_s, prompt token ids, output tokens)]`` of a serving mix, in
    the order they are sent.

    Arrivals and lengths come from the mix's ``lengths_seed`` alone. The
    seed draws the token ids, uniform over the vocabulary, so no prompt
    shares a prefix with another, unless the mix has ``shared_prefix``
    (``{"groups": g, "tokens": t}``): then each request belongs to one of
    ``g`` groups and its prompt starts with that group's ``t`` tokens, the
    drawn length being of what follows them."""
    fixed = np.random.default_rng([int(traffic["lengths_seed"]), 1])
    arrivals = traffic["arrivals"]
    due = ARRIVAL_LAWS[arrivals["law"]](fixed, float(seconds), arrivals)
    if len(due) > DRAWN:
        raise ValueError(f"{len(due)} requests; at most {DRAWN} are drawn")
    lengths = {}
    for stream, part in enumerate(("prompt_tokens", "output_tokens")):
        spec = traffic[part]
        lengths[part] = LENGTH_LAWS[spec["law"]](
            np.random.default_rng([int(traffic["lengths_seed"]), 2 + stream]),
            DRAWN, spec)[:len(due)]
    seed = int(seed)
    ids = np.random.default_rng([seed & 0x7FFFFFFF, seed >> 31, 4])
    shared = traffic.get("shared_prefix")
    groups = member = None
    if shared:
        groups = ids.integers(0, vocab_size,
                              (shared["groups"], shared["tokens"]))
        member = fixed.integers(0, shared["groups"], DRAWN)
    out = []
    for i, (t, p, o) in enumerate(zip(due.tolist(),
                                      lengths["prompt_tokens"].tolist(),
                                      lengths["output_tokens"].tolist())):
        prompt = ids.integers(0, vocab_size, p).tolist()
        if shared:
            prompt = groups[member[i]].tolist() + prompt
        out.append((t, prompt, o))
    return out

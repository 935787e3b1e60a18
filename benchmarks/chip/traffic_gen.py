"""The one traffic generator: the arrival streams of the replay cells.

A mix (``traffic/<mix>.json``) names an arrival law, its rate, the size
of a replay call (``jobs`` arrivals per trial, ``trials`` trials per
call) and a pool of ``pool_calls`` call seeds from ``pool_seed`` on.
The replay engine under test draws each trial's stream on the device
from the trial's key; this module holds that key schedule and the law,
so that the plain reference can draw the same streams without the
program, and a change to the program cannot move the yardstick:

* a run's seed orders the pool (:func:`pool_order`), and call ``c`` of
  the run (0 is the warm-up) replays the trials of the pool's call seed
  ``s_c`` at place ``c`` in that order, round and round
  (:func:`call_seed`): every run replays the same trials, in another
  order, so the seed does not change the amount of work;
* trial ``t`` of call seed ``s`` has the key
  ``split(PRNGKey(2 * s + 1), trials)[t]``, which splits five ways:
  arrivals, service, failure, overhead, priority;
* ``poisson``: the trial's arrivals are the running sum of ``jobs``
  exponential gaps of mean ``1000 / rate_hz`` ms, in float32, starting
  from an idle cluster at 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LAWS = ("poisson",)


def check_mix(mix: dict) -> None:
    if mix["arrival"] not in LAWS:
        raise ValueError(f"unknown arrival law {mix['arrival']!r}; "
                         f"known: {LAWS}")
    if not float(mix["rate_hz"]) > 0.0:
        raise ValueError(f"rate_hz must be positive, got {mix['rate_hz']}")
    if int(mix["jobs"]) < 1 or int(mix["trials"]) < 1:
        raise ValueError("a call replays at least one job and one trial")
    if int(mix["pool_calls"]) < 1:
        raise ValueError("the pool holds at least one call seed")
    if not 0 <= int(mix["pool_seed"]) < (1 << 30) - int(mix["pool_calls"]):
        raise ValueError("call seeds must fit PRNGKey(2 * s + 1)")


def pool_order(mix: dict, seed: int) -> np.ndarray:
    """The order of the pool's call seeds in the run with this seed (any
    whole number)."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    return int(mix["pool_seed"]) + rng.permutation(int(mix["pool_calls"]))


def call_seed(order, call: int) -> int:
    """The seed of call ``call`` of a run whose pool order is ``order``."""
    return int(order[int(call) % len(order)])


def trial_keys(seed: int, trials: int):
    """The ``trials`` keys of one call, from its call seed."""
    return jax.random.split(jax.random.PRNGKey(2 * int(seed) + 1), trials)


def split_trial_key(key):
    """(arrivals, service, failure, overhead, priority) keys of a trial."""
    return jax.random.split(key, 5)


def poisson_arrivals(key, jobs: int, rate_hz):
    """One trial's absolute arrival times (ms, float32, sorted)."""
    return jnp.cumsum(jax.random.exponential(key, (jobs,))
                      * (1000.0 / rate_hz))

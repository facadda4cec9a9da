"""The one traffic generator: rounds of requests, from a mix's parameters.

A mix (``bench/traffic/<name>.json``) is data:

``round_size``
    requests in one round, each round one ``ContinuousServingRuntime.run``;
``spacing_s``
    arrival spacing inside a round on the runtime's arrival clock: 0 puts
    every request in the queue at the round's start (a backlog); a spacing
    far above any service time makes the runtime serve them strictly one at
    a time, back to back (one closed-loop client, no think time);
``rounds``
    rounds in one cycle; the window runs through the cycle and starts it
    again.

Groups are drawn uniformly, in shuffled blocks that hold every serve group
once, so that every round sends each group equally often.  The rounds are
drawn once, the same for every seed, and the seed puts them in its own
order.  Under continuous batching the order inside a round decides how many
chunk dispatches its iterating requests share, so rounds drawn from the
seed would change the work and not only its order.
"""
from __future__ import annotations

import numpy as np


def check(mix: dict) -> dict:
    """Raise unless ``mix`` holds the parameters this generator reads."""
    need = {"round_size", "spacing_s", "rounds"}
    if set(mix) != need:
        raise ValueError(f"traffic mix keys {sorted(mix)} are not {sorted(need)}")
    if int(mix["round_size"]) < 1 or int(mix["rounds"]) < 1:
        raise ValueError("round_size and rounds must be >= 1")
    if float(mix["spacing_s"]) < 0:
        raise ValueError("spacing_s must be >= 0")
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per use of the seed (any size of seed)."""
    return np.random.default_rng([int(seed) % 2**63, stream])


def group_draws(n_groups: int, count: int, rng: np.random.Generator) -> np.ndarray:
    """``count`` group indices: shuffled blocks that each hold every group."""
    blocks = -(-count // n_groups)
    return np.concatenate([rng.permutation(n_groups) for _ in range(blocks)])[:count]


def rounds(mix: dict, group_requests: list[dict], seed: int) -> list[list[tuple]]:
    """The cycle of rounds of ``(t, request)`` arrivals, in ``seed``'s order.

    ``group_requests[g]`` is the request that names serve group ``g``.
    """
    check(mix)
    size, n = int(mix["round_size"]), int(mix["rounds"])
    draws = group_draws(len(group_requests), size * n, rng_for(0, 1))
    spacing = float(mix["spacing_s"])
    cycle = [
        [(i * spacing, group_requests[g])
         for i, g in enumerate(draws[r * size:(r + 1) * size])]
        for r in range(n)
    ]
    return [cycle[r] for r in rng_for(seed, 1).permutation(n)]

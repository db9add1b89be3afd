"""The ``interference`` stimulus, for the plain reference.

A copy of the arithmetic of the program's ``interference`` generator, kept
here so that the reference gets its inputs from the benchmark and not from
the program.  The program builds its own copy of the same inputs from the
same parameters inside ``ExperimentSpec.run``; the reference comparison
(``app_arrive`` among the leaves) shows that both builds agree.
"""
import numpy as np

INF = 1e18
MAX_LEN = 16_000.0


def generate(max_apps: int, n_childs: int, k: int, *, sim_len: float,
             seed: int, pair_period: float, lam: float = 7_999.0,
             active_frac: float = 0.9):
    """Two competing application streams (paper Fig 4): a pair every
    ``pair_period`` ticks, the second offset by an exponential draw of
    mean ``lam``, each entering at a uniform random GMN; child lengths
    uniform in 95-100% of MAX_LEN.  Returns (arrivals, gmns, lengths)."""
    rng = np.random.default_rng(seed)
    n_pairs = int(active_frac * sim_len / pair_period)
    n_apps = min(2 * n_pairs, max_apps - 2)
    arrivals = np.full((max_apps,), INF, np.float32)
    gmns = np.zeros((max_apps,), np.int32)
    i, t = 0, 0.0
    while i + 1 < n_apps:
        arrivals[i] = t
        arrivals[i + 1] = t + rng.exponential(lam)
        gmns[i] = rng.integers(0, k)
        gmns[i + 1] = rng.integers(0, k)
        i += 2
        t += pair_period
    lengths = rng.uniform(0.95 * MAX_LEN, MAX_LEN,
                          (max_apps, n_childs)).astype(np.float32)
    return arrivals, gmns, lengths

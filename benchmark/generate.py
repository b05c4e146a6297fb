"""The one generator of every cell's inputs.  A traffic mix is a data file
of parameters (``benchmark/traffic/<name>.json``); everything here is a pure
function of those parameters and ``--seed``.  Nothing of the program is
imported: the program receives only what is generated.

Every seed gives the same *set* of sizes and arrivals in another order (and
other pixel, token and label values), so that no seed changes the work.
"""

import numpy as np


def _rng(seed, *stream):
    return np.random.default_rng([int(seed)] + [int(s) for s in stream])


# -- training rows ----------------------------------------------------------

def image_row(seed, index, traffic):
    """(image uint8 [store, store, 3], label, index, cropx, cropy, flip): row
    ``index`` of the seeded image table (``traffic``: whatever holds
    ``store_px``, ``image_size`` and ``num_classes``).  Every row has a
    generator of its own, so any row can be made again, alone, by whoever
    checks it."""
    rng = _rng(seed, 1, index)
    px, crop = traffic["store_px"], traffic["image_size"]
    image = rng.integers(0, 256, (px, px, 3), dtype=np.uint8)
    label = int(rng.integers(1, traffic["num_classes"]))
    cropx, cropy = (int(v) for v in rng.integers(0, px - crop + 1, 2))
    return (image, label, int(index), cropx, cropy, int(rng.integers(0, 2)))


def token_rows(seed, traffic, start, count):
    """int32 [count, 1 + seq]: column 0 is the row's index, the rest its
    tokens, uniform over the vocabulary (a row is its own generator's)."""
    seq, vocab = traffic["seq_len"], traffic["vocab_size"]
    out = np.empty((count, 1 + seq), np.int32)
    for j in range(count):
        out[j, 0] = start + j
        out[j, 1:] = _rng(seed, 2, start + j).integers(0, vocab, seq)
    return out


# -- open-loop serving ------------------------------------------------------

def _spread(weights, n):
    """``n`` items split over the keys of ``weights`` in proportion, exactly
    (largest remainders), so that every seed sends the same multiset."""
    keys = sorted(weights, key=int)
    total = float(sum(weights.values()))
    exact = [n * weights[k] / total for k in keys]
    counts = [int(e) for e in exact]
    order = sorted(range(len(keys)), key=lambda i: exact[i] - counts[i],
                   reverse=True)
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return [int(k) for k, c in zip(keys, counts) for _ in range(c)]


def poisson_gaps(n, rate, seconds):
    """The ``n`` mid-quantiles of an exponential distribution's gaps at
    ``rate``, scaled to fill ``seconds``: the fixed set every seed shuffles."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    return gaps * (seconds / gaps.sum())


def arrivals(seed, traffic, seconds, rate=None):
    """[(due seconds from the window's start, images)] of an open loop at a
    fixed rate: the gaps are the ``n`` mid-quantiles of the exponential
    distribution (a Poisson process's gaps), the sizes an exact split by the
    mix's weights; the seed only shuffles both."""
    rate = float(rate or traffic["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    rng = _rng(seed, 3)
    gaps = poisson_gaps(n, rate, seconds)
    rng.shuffle(gaps)
    sizes = np.asarray(_spread(traffic["size_weights"], n))
    rng.shuffle(sizes)
    due = np.cumsum(gaps) - gaps[0]
    return [(float(t), int(s)) for t, s in zip(due, sizes)]


def image_pool(seed, traffic, size):
    """float32 [pool_images, size, size, 3]: the distinct images of a run,
    mean-subtracted pixels as a client of the export sends them."""
    rng = _rng(seed, 4)
    x = rng.integers(0, 256, (traffic["pool_images"], size, size, 3),
                     dtype=np.uint8)
    return x.astype(np.float32) - np.float32(117.0)


def request_images(seed, request, images, pool):
    """The ``images`` images of request number ``request``: a seeded draw
    from the pool, so that any request can be made again by whoever checks
    its reply."""
    return pool[_rng(seed, 5, request).integers(0, len(pool), images)]


def sample_requests(seed, schedule, traffic):
    """The requests whose replies are compared with the reference: a seeded
    ``sample_fraction`` of them (at least ``sample_min``), the first of the
    largest size always among them."""
    n = len(schedule)
    k = min(n, max(traffic["sample_min"],
                   int(round(n * traffic["sample_fraction"]))))
    chosen = set(int(i) for i in _rng(seed, 6).choice(n, k, replace=False))
    largest = max(s for _, s in schedule)
    chosen.add(next(i for i, (_, s) in enumerate(schedule) if s == largest))
    return chosen

"""Row chunks across threads: numpy releases the interpreter lock inside
its loops, so a pool of threads uses the host's cores."""
import os
from concurrent.futures import ThreadPoolExecutor


def threads():
    return max(1, min(len(os.sched_getaffinity(0)), 32))


def fixed_bounds(n, chunk):
    return list(range(0, n, chunk)) + [n]


def even_bounds(n, min_chunk=1 << 12, max_chunk=1 << 18):
    """[0, ..., n]: a chunk a thread where that lies between the two
    sizes (small enough to stay in cache, large enough to be worth a
    task)."""
    chunk = min(max_chunk, max(min_chunk, -(-n // threads())))
    return fixed_bounds(n, chunk)


def for_chunks(bounds, fn):
    """`fn(i, lo, hi)` for every chunk i = [lo, hi), on the pool; an
    exception in any chunk is raised here."""
    with ThreadPoolExecutor(threads()) as pool:
        list(pool.map(lambda i: fn(i, bounds[i], bounds[i + 1]),
                      range(len(bounds) - 1)))

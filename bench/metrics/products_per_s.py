"""products_per_s: semiring products of every batch the window counted,
over the time from the first call's start to the last counted arrival
(host clock). Products are the benchmark's own count from the host
operands (``bench/counting.py``), the same whatever path the program runs."""


def read(ctx):
    win = ctx.window
    if not win.batches:
        return None
    products = sum(ctx.counts.products(b.columns) for b in win.batches)
    return products / win.elapsed

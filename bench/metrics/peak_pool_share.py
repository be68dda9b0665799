"""The most K/V pool pages in use at once during the window, as a share of
the pool (the program's ``EngineStats.pages_in_use`` over ``pool_pages``,
read after every loop turn)."""


def read(run):
    if not run.pool_pages:
        return None
    return 100.0 * run.peak_pages / run.pool_pages

"""Peak pages in use over the window (sampled by the dispatcher from
``PagedKVCache.pages_in_use``) over the pages a request can own."""


def read(inputs):
    n = inputs["facts"].get("n_pages")
    if not n or n < 2:
        return None
    return 100.0 * inputs["counters"]["kv_pages_peak"] / (n - 1)

"""Device decode launches (``kernels.bitunpack.decode_stats()["calls"]``)
per request, over the window."""


def read(obs: dict):
    c = obs["counters"]
    if not c.get("requests"):
        return None
    return c["decode_calls"] / c["requests"]

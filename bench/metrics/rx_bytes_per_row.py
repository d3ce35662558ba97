"""Bytes the client received from the OSDs (``Fabric.client_rx``) per
result row, over the window."""


def read(obs: dict):
    c = obs["counters"]
    if not c.get("result_rows"):
        return None
    return c["client_rx_bytes"] / c["result_rows"]

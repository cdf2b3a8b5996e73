"""KV manager: bytes of the engine's cache resident at the end of the
window (``kv_bytes_resident``), in GB."""


def read(view):
    n = (view.get("serving_stats") or {}).get("kv_bytes_resident")
    return n / 1e9 if n else None

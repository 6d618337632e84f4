"""Serving-cache memory per request: the bytes of the KV leaves and of the
recurrent-state leaves of every cache a prefill returned in the window
(``ServeSession``'s counters ``serve.cache_bytes:kv`` and
``serve.cache_bytes:recurrent``, counted from the leaves' shapes and
dtypes), over the requests prefilled, in MB."""


def read(rec):
    c = rec.get("counters") or {}
    kv = c.get("serve.cache_bytes:kv")
    state = c.get("serve.cache_bytes:recurrent")
    if kv is None or state is None or not rec.get("requests"):
        return None
    return (kv + state) / rec["requests"] / 1e6

"""Backend compile seconds during set-up, from JAX's monitoring events;
loads from the persistent cache are counted (they record the event with
the load time)."""


def read(r):
    if r.compile is None:
        return None
    return r.compile[0]

"""Algorithm-3 iterations (``QoIRetrievalResult.iterations``, summed over
the request's taus) per QoI request of the window."""


def read(r):
    units = r.counters.get("units", 0)
    if not units:
        return None
    return r.counters["iterations"] / units

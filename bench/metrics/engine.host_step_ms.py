"""Host time per decode step (ms), outside the device wait: the summed
in-window durations of the driver thread's spans ``server.ingress``,
``engine.plan``, ``engine.dispatch_chunk``, ``engine.dispatch_decode``
and ``engine.resolve`` over the count of ``engine.dispatch_decode``
spans.  The program keeps these spans from nesting, so the sum is the
host's self time; the blocking fetch of a step's tokens
(``engine.harvest_wait``) is left out.  None where the program records
no such span."""

from bench import trace as T

#: the host spans of runtime/server.py and runtime/engine.py
HOST = (r"^(server\.ingress|engine\.(plan|dispatch_chunk|dispatch_decode"
        r"|resolve))( |$)")
DECODE = r"^engine\.dispatch_decode( |$)"


def read(r):
    if r.trace is None:
        return None
    _, n = T.seconds(r.trace.host, DECODE, r.trace)
    s, _ = T.seconds(r.trace.host, HOST, r.trace)
    return 1e3 * s / n if n else None

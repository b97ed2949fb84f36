"""Host time the engine works per ``engine.tick()``, in milliseconds:
the ``serve.tick`` spans of the traced slice less the spans in which the
host waits on the device, over the ticks (serve/engine.py spans on the
profiler's clock).  The waits are the two syncs
(``serve.first_token_sync``, ``serve.decode_sync``) and the prefill
chunks' uploads and launches (``serve.prefill_chunk``): the runtime holds
a chunk's uploads until the device has run the chunks queued before it,
about 16 ms a chunk on a v5e in the chat cell against about 1 ms when
nothing is queued.  That host work of a chunk is left out; the decode
launch and its uploads (``serve.dispatch_args``), which do not wait,
count.  None where the trace holds no such spans."""
from bench import engine_trace

engine_trace.install()

WAITS = ("serve.first_token_sync", "serve.decode_sync",
         "serve.prefill_chunk")


def read(ctx):
    spans = (ctx.trace or {}).get("engine_spans") or {}
    tick = spans.get(engine_trace.TICK)
    if not tick:
        return None
    wait = sum(spans[n]["seconds"] for n in WAITS if n in spans)
    return 1e3 * (tick["seconds"] - wait) / tick["calls"]

"""Names of the serving loop's host spans.

Each is a ``jax.profiler.TraceAnnotation`` around one part of a tick's
host work.  A span records only while a profiler runs (``jax.profiler.
start_trace``, or ``python -m repro.launch.serve ... --trace-dir DIR``),
on the profiler's host plane, whose clock the device's op events share;
with no profiler running it costs about a microsecond and records nothing.

The jitted steps name their parts with ``jax.named_scope`` instead
(``kv_write``, ``kv_gather``, ``attention``, ``mlp``, ``head``; on a TPU
decode attention is one paged kernel under ``attention``, with no
``kv_gather``): those reach the compiled HLO's ``op_name`` metadata, not
the host plane.
"""

#: the front end taking one request (stat ``uid``)
SUBMIT = "serve.submit"
#: routing and placement of the fleet's pending requests
ROUTE = "serve.route"
#: one engine tick's slot grants
ADMIT = "serve.admit"
#: the instant one request is granted a slot (stat ``uid``)
ADMITTED = "serve.admitted"
#: host part of a prompt chunk: page growth, uploads, ``chunk_fn`` dispatch
PREFILL = "serve.prefill"
#: host part of a decode step: page growth, tables, uploads, ``decode_fn``
#: dispatch
DECODE = "serve.decode"
#: the host blocked on the device's sampled tokens
SYNC = "serve.sync"
#: after the decode sync: token appends, finishes, page release
COMMIT = "serve.commit"
#: the front end streaming new tokens to their handles
DRAIN = "serve.drain"

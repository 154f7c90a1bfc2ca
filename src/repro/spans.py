"""Names of the program's profiler spans on the transcode path.

Each site writes its span with ``jax.profiler.TraceAnnotation(<name>)``.
With no profiler session active a span costs about a microsecond; under
``jax.profiler.start_trace`` it is an event of the host plane, on the
same clock as the device's operations.  Every name starts with
``repro.``.
"""

# The whole of ``core.transcode.transcode``, whatever the strategy.
TRANSCODE = "repro.transcode"
# ``transcode_onepass``: argument conversion to the return of the jitted call.
TRANSCODE_DISPATCH = "repro.transcode.dispatch"
# The whole of ``core.transcode.ragged_transcode``.
RAGGED_TRANSCODE = "repro.ragged_transcode"
# ``ragged_transcode._as_packed``: conversion and the layout check's readbacks.
RAGGED_CHECK_LAYOUT = "repro.ragged.check_layout"
# ``transcode_ragged``: the call of the jitted batch program.
RAGGED_DISPATCH = "repro.ragged.dispatch"
# The body of ``core.packing.pack_documents``.
PACK_DOCUMENTS = "repro.pack_documents"

"""Names of the profiler scopes and spans the engine records.

Device work is named with ``jax.named_scope``: the name lands in each
HLO op's metadata, and a TPU trace carries it in the op's ``tf_op``
stat (``jit(_run)/while/body/des.extract/...``).  Host work is named
with ``jax.profiler.TraceAnnotation``, on the profiler's clock beside
the device ops.  Both cost nothing measurable with the profiler off: a
scope is compile-time metadata, a span about a microsecond of host time.

Where scopes nest (the merge runs inside the insert and the absorb),
the innermost ``des.*`` name owns the op.  ``bench/scopes.py`` reduces
a trace by these names.
"""

# -- device scopes: the legs of one super-step ----------------------------
EXTRACT = "des.extract"    # window extraction, front refill included
DISPATCH = "des.dispatch"  # the composed batch: switch or entity run path
INSERT = "des.insert"      # the emit insert (and its spill diversion)
MERGE = "des.merge"        # pool merge and rotate: linear passes over main
ABSORB = "des.absorb"      # arrival and spill reabsorb; also a host span

# -- host spans of the segment loop (``CompiledSim._segment_loop``) -----
SEGMENT = "des.segment"      # one engine.run call and the boundary read
BOUNDARY = "des.boundary"    # the loop's work between two segments
OCCUPANCY = "des.occupancy"  # admission room from the boundary read
FENCE = "des.fence"          # admission-fence refresh (may wait for a block)
NEXT_TIME = "des.next_time"  # earliest outstanding time vs the horizon
SPILL = "des.spill"          # spill pool reabsorb and drain
CHECKPOINT = "des.checkpoint"  # async checkpoint save

# -- host span on the stream feeder's thread -------------------------------
FEEDER_STAGE = "des.feeder.stage"  # build one arrival block and device_put it

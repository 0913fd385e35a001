"""Pipeline step (``repro.pipeline.run_pipeline``, the scanned chunk the
window drives): host-clock ms per tick over whole chunks of the cell's
rows, on a copy of the state kept mid-segment (the call donates it), synced
at the end of each block of chunks."""


def read(run):
    prog = run.program
    rows = run.traffic.chunk(run.segment_key, run.next_tick)

    def make_call():
        box = [run.state_copy()]

        def call():
            box[0], admitted = prog.run_chunk(box[0], *rows)
            return admitted
        return call
    return run.per_call_ms(make_call) / run.traffic.chunk_ticks

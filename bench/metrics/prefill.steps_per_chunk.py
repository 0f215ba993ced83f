"""prefill.steps_per_chunk: serving-loop steps spent prefilling over prompt
chunks run, over the requests due in the window whose whole prompt was in
by its close: the sum of ``ready_step - admit_step + 1`` over the sum of
``prefill_chunks`` (program ledger). 1.0 means every request's chunks ran
back to back; more, that they waited behind other slots' chunks."""
from bench.ledger import due_in_window


def read(ctx):
    reqs = due_in_window(ctx.window)
    if reqs is None:
        return None
    ready = [r for r in reqs if r.ready_step is not None]
    chunks = sum(r.prefill_chunks for r in ready)
    if chunks == 0:
        return None
    steps = sum(r.ready_step - r.admit_step + 1 for r in ready)
    return steps / chunks, f"{len(ready)} requests, {chunks} chunks"

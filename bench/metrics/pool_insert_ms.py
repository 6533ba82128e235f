"""Host time per round of the window in the device data pool's inserts,
milliseconds: the program's ``fl.data-pool.insert`` spans (each builds the
new clients' padded rows, evicts, stacks and uploads them), summed.
Nothing where the program has no such span."""
import spans


def read(ctx):
    return spans.per_round_ms(ctx, ("fl.data-pool.insert",))

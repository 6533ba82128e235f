"""Host time per round of the window in round prep, milliseconds: the
program's ``fl.cohort`` (selection, the clients and their data) and
``fl.inputs`` (batch indices, the data pool's gather and inserts, FedAvg
weights, the error-feedback rows, the program lookup) spans, summed.
Nothing where the program has no such spans."""
import spans


def read(ctx):
    return spans.per_round_ms(ctx, spans.PREP)

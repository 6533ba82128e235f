"""Share of the traced window in which the chip runs no operation while
the host is in round prep (the program's ``fl.cohort`` or ``fl.inputs``
span), in percent, averaged over the cell's chips: the part of
``device_idle_share`` that round prep holds the chip back.  Nothing where
the program has no such spans."""
import spans


def read(ctx):
    return spans.idle_inside_share(ctx, spans.PREP)

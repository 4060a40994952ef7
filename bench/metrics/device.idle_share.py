"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""


def read(trace, ctx):
    return 100.0 * trace["idle_share"]

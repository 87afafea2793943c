"""Mean ticks from a reconfiguration's injection (the first tick of the
dispatch it rides in) to the tick whose epoch switch committed, by the
step's own per-tick ``switched`` flags, over the reconfigurations whose
switch was delivered inside the window."""


def read(ctx):
    ticks = ctx["run"]["switch_ticks"]
    return sum(ticks) / len(ticks) if ticks else None

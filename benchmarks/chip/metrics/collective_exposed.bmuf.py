"""Share of the traced window in which a collective ran on a chip and no
other op did (``Trace.exposed_collective_s``: the BMUF block all-reduce
left uncovered by compute), averaged over the chips used."""


def read(run, res, tr):
    if tr.window_s() <= 0 or not tr.devices():
        return None
    return 100.0 * tr.exposed_collective_s() / tr.window_s()

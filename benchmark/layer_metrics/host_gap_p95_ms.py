"""95th percentile of the device-idle gaps between consecutive runs of the
step program on the fullest device, in ms."""


def read(trace, facts):
    return 1e3 * trace["gap_p95_s"]

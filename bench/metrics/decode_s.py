"""Frame unpack, CRC32C check and decode per apply: the mean of
``StreamReplica.poll``'s own ``decode`` seconds (spans
``repro.stream.decode``) over the window's applies."""


def read(run):
    walls = [r["timings"]["decode"] for r in run.rebuilds if "decode" in r["timings"]]
    return sum(walls) / len(walls) if walls else None

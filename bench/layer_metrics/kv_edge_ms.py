"""Host milliseconds per prompt-KV transfer on the prefill -> decode edge:
the connector's ``stats.wall_time`` (pack on send plus unpack on recv)
over its ``stats.calls``, both as they moved in the window.  It leaves
out the device side of the hop (``extract_kv`` and ``inject_kv``)."""


def read(run):
    if "conn@open" not in run.snapshots:
        return None
    calls = run.delta("conn", "calls")
    return run.delta("conn", "wall") / calls * 1e3 if calls else None

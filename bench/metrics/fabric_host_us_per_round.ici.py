"""Host time per exchange outside the collective transport's
``deliver`` (which dispatches the device program and waits for it):
the stub calls and the rest of ``RpcFabric.flush``, from the
benchmark's wrappers, as a mean over the window."""


def read(run):
    s = run.system
    if not s.exchanges:
        return None
    total = sum(b - a for a, b in s.exchanges)
    inside = sum(b - a for a, b in s.delivers)
    return (total - inside) / len(s.exchanges) * 1e6

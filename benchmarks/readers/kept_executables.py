"""Every executable this process compiles, kept alive until the readers run.

Not a reader: `device_time_in_scope` imports it.  A kind drops its engines
before it returns and the readers run after that, so by then the client has
let go of the programs whose HLO names the scopes.  The harness has no hook
for a reader before the cell runs; importing this module is that hook.  It
registers ONE `jax.monitoring` listener (Python imports a module once), and
the loader executes reader files before the kind runs, whatever `--trace`
says.  So an untraced run carries it too: on every backend compile it asks
the client for its live executables and pins the new ones in `_KEPT` for the
life of the process (the programs of one cell, which the run holds anyway
while it measures).  Nothing compiles inside a measured window, so the
listener does not run there.
"""

import jax
import jax.monitoring

from benchmarks.lib.device import BACKEND_COMPILE_EVENT

# id -> executable.  The client hands back the same object for an executable
# each time, and an object that is kept keeps its id.
_KEPT = {}


def keep(event=BACKEND_COMPILE_EVENT, *_, **__):
    if event != BACKEND_COMPILE_EVENT:
        return
    for ex in jax.devices()[0].client.live_executables():
        _KEPT.setdefault(id(ex), ex)


def hlo_texts(module_names):
    """{module name: [optimised HLO text]} of the kept executables whose
    module name is among `module_names` (only those are printed)."""
    keep()
    out = {}
    for ex in _KEPT.values():
        try:
            mod = ex.hlo_modules()[0]
        except Exception:  # noqa: BLE001 — an executable without HLO
            continue
        if mod.name in module_names:
            out.setdefault(mod.name, []).append(mod.to_string())
    return out


jax.monitoring.register_event_duration_secs_listener(keep)

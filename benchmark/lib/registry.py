"""Name -> function tables that the data files refer to.

``configs/<c>.json`` names a *builder* (how the system under test is put
together) and an *arch* (how its FLOPs and bytes are counted);
``traffic/<t>.json`` names a *loop* (how load is offered);
``end_to_end/<m>.json`` and ``layer_metrics/<m>.json`` name a *reader*
(how a number is taken from series, counters and the trace). A later PR
adds a kind by adding a file to this directory: ``load_all`` imports
every module here, and the decorators below fill the tables.
"""

import importlib
import pkgutil

BUILDERS = {}
LOOPS = {}
READERS = {}
ARCHS = {}


def _register(table, what):
    def named(name):
        def deco(fn):
            if name in table:
                raise ValueError(f"{what} {name!r} is registered twice")
            table[name] = fn
            return fn
        return deco
    return named


builder = _register(BUILDERS, "builder")
loop = _register(LOOPS, "loop")
reader = _register(READERS, "reader")
arch = _register(ARCHS, "arch")


def lookup(table, name, what):
    try:
        return table[name]
    except KeyError:
        raise KeyError(f"no {what} named {name!r}; known: "
                       f"{sorted(table)}") from None


def load_all():
    """Import every module of ``benchmark.lib`` once."""
    import benchmark.lib as pkg
    for mod in pkgutil.iter_modules(pkg.__path__):
        importlib.import_module(f"benchmark.lib.{mod.name}")

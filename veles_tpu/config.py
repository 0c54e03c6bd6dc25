"""Global configuration tree.

Re-designs the reference's auto-vivifying ``root`` config
(``veles/config.py:60-325``): attribute access creates nested nodes on
demand (``root.loader.minibatch_size = 60``), config files are plain
Python that mutates ``root``, ``update()`` deep-merges dicts, keys can be
``protect()``-ed against further writes, and site override files are
applied at import. An attribute that was merely *read* (auto-vivified)
is an empty node: ``validate()`` and ``get()`` treat it as undefined, so
typos in workflow configs fail fast instead of training with defaults.
"""

import os
import runpy
import threading

from veles_tpu.envknob import env_knob


class Config(object):
    """One node of the configuration tree.

    Attribute reads auto-vivify child nodes; reading a node where a value
    was expected raises ``AttributeError`` from :meth:`validate` (the
    reference's undefined-leaf detection, ``veles/config.py:165-176``).
    """

    __slots__ = ("__dict__",)

    def __init__(self, path="root", **values):
        object.__setattr__(self, "__dict__", {
            "_path_": path, "_protected_": set()})
        for key, value in values.items():
            setattr(self, key, value)

    # -- tree construction ------------------------------------------------

    def __getattr__(self, name):
        if name.startswith("_") and name.endswith("_"):
            raise AttributeError(name)
        child = Config("%s.%s" % (self._path_, name))
        self.__dict__[name] = child
        return child

    def __setattr__(self, name, value):
        if name in self._protected_:
            raise AttributeError(
                "config key %s.%s is protected" % (self._path_, name))
        if isinstance(value, dict):
            node = self.__dict__.get(name)
            if not isinstance(node, Config):
                node = Config("%s.%s" % (self._path_, name))
                self.__dict__[name] = node
            node.update(value)
            return
        self.__dict__[name] = value

    # -- dict-ish access --------------------------------------------------

    def __getitem__(self, name):
        return getattr(self, name)

    def __setitem__(self, name, value):
        setattr(self, name, value)

    def __contains__(self, name):
        return name in self.keys()

    def keys(self):
        return [k for k, v in self.__dict__.items()
                if not (k.startswith("_") and k.endswith("_"))]

    def items(self):
        return [(k, self.__dict__[k]) for k in self.keys()]

    @staticmethod
    def _is_defined(value):
        # an empty Config child means the name was only ever *read*
        return not (isinstance(value, Config) and not value.keys())

    def get(self, name, default=None):
        """Read a leaf without vivifying it."""
        value = self.__dict__.get(name, default)
        return value if Config._is_defined(value) else default

    def update(self, tree):
        """Deep-merge a dict (or another Config) into this node."""
        if isinstance(tree, Config):
            tree = tree.to_dict()
        if not isinstance(tree, dict):
            raise TypeError("update() needs a dict, got %s" % type(tree))
        for key, value in tree.items():
            setattr(self, key, value)
        return self

    def to_dict(self):
        out = {}
        for key, value in self.items():
            out[key] = value.to_dict() if isinstance(value, Config) else value
        return out

    # -- integrity --------------------------------------------------------

    def protect(self, *names):
        """Forbid future writes to the named direct children."""
        self._protected_.update(names)

    def validate(self, *required):
        """Raise if any of the named leaves was never assigned."""
        missing = [n for n in required
                   if n not in self.__dict__ or
                   not Config._is_defined(self.__dict__[n])]
        if missing:
            raise AttributeError(
                "undefined config value(s) %s under %s" %
                (", ".join(missing), self._path_))

    def print_(self, indent=0, file=None):
        import sys
        file = file or sys.stdout
        for key, value in sorted(self.items()):
            if isinstance(value, Config):
                print("%s%s:" % ("  " * indent, key), file=file)
                value.print_(indent + 1, file)
            else:
                print("%s%s: %r" % ("  " * indent, key, value), file=file)

    def __repr__(self):
        return "<Config %s: %s>" % (self._path_, ", ".join(self.keys()))

    # Config nodes appear inside pickled workflows (snapshots).
    def __getstate__(self):
        return {"path": self._path_, "tree": self.to_dict()}

    def __setstate__(self, state):
        object.__setattr__(self, "__dict__", {
            "_path_": state["path"], "_protected_": set()})
        self.update(state["tree"])


#: The global configuration tree every workflow/config file mutates.
root = Config("root")

_PACKAGE_DIR = os.path.dirname(os.path.abspath(__file__))

#: Default root of every persistent cache (XLA executables, generated
#: datasets): one fixed, git-ignored directory in
#: the checkout, next to the package. A cache that moves between two
#: runs never hits, and a sealed machine keeps nothing outside the
#: checkout, so the path is derived from the package's location alone —
#: never from ``$HOME``, a temporary name, a pid or the time.
CACHE_ROOT = os.path.join(os.path.dirname(_PACKAGE_DIR), ".veles_cache")

_config_lock = threading.Lock()


def _init_defaults():
    """Platform defaults (the reference's ``veles/config.py:178-291``)."""
    home = os.path.join(os.path.expanduser("~"), ".veles_tpu")
    root.common.update({
        "dirs": {
            "veles": _PACKAGE_DIR,
            "user": home,
            "cache": CACHE_ROOT,
            "snapshots": os.path.join(home, "snapshots"),
            "datasets": os.path.join(home, "datasets"),
        },
        "engine": {
            "backend": env_knob("VELES_TPU_BACKEND", "auto"),
            # fp precision policy: compute dtype for MXU matmuls and the
            # accumulation discipline replacing the reference's
            # PRECISION_LEVEL 0/1/2 (``veles/config.py:244-248``).
            "precision_type": env_knob("VELES_PRECISION", "float32"),
            "precision_level": env_knob("VELES_PRECISION_LEVEL", 0,
                                        parse=int),
        },
        "trace": {"run": False, "misprints": False},
        "timings": False,
        "exceptions": {"run_after_stop": True},
        "disable": {"plotting": "DISPLAY" not in os.environ,
                    "publishing": False, "snapshotting": False},
        "random_seed": None,
        "web": {"host": "localhost", "port": 8090,
                "notification_interval": 1.0},
        "api": {"host": "localhost", "port": 8180, "path": "/api"},
        "forge": {"service_name": "forge", "manifest": "manifest.json"},
        "ensemble": {"model_index": 0, "size": 0, "train_ratio": 1.0},
        "graphics": {"multicast_address": "239.192.1.1", "blacklisted_ifs": []},
    })


def apply_config_file(path, context=None):
    """Execute a Python config file that mutates ``root``.

    The reference runs config files via ``runpy`` with ``root`` injected
    (``veles/__main__.py:426-472``); same contract here.
    """
    with _config_lock:
        runpy.run_path(path, init_globals=dict(
            {"root": root}, **(context or {})))
    return root


def apply_overrides(pairs):
    """Apply CLI ``key=value`` overrides (evaluated as Python literals)."""
    import ast
    for pair in pairs:
        key, _, expr = pair.partition("=")
        if not _:
            raise ValueError("override %r is not key=value" % pair)
        try:
            value = ast.literal_eval(expr)
        except (ValueError, SyntaxError):
            value = expr
        node = root
        parts = key.strip().split(".")
        if parts[0] == "root":
            parts = parts[1:]
        for part in parts[:-1]:
            node = getattr(node, part)
        setattr(node, parts[-1], value)


def _apply_site_overrides():
    """Site override chain (``veles/config.py:293-308``): /etc, home, CWD."""
    for candidate in ("/etc/default/veles_tpu",
                      os.path.join(os.path.expanduser("~"), ".veles_tpu",
                                   "site_config.py"),
                      os.path.join(os.getcwd(), "site_config.py")):
        if os.path.isfile(candidate):
            try:
                apply_config_file(candidate)
            except Exception as exc:  # site files must never brick startup
                import logging
                logging.getLogger("config").warning(
                    "failed to apply site config %s: %s", candidate, exc)


_init_defaults()
_apply_site_overrides()

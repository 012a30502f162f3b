"""`GenEngine.__init__` takes what somebody sets (`tests/test_engine_options.py`);
what it used to take besides is a constant of `gen/engine.py` behind an
instance attribute.  A test that needs another value sets the attribute
after construction, before the first step: this helper does, for the
keywords the constructor does not know."""

import inspect

from areal_tpu.gen.engine import GenEngine


def build_engine(cfg, params, **kw):
    taken = inspect.signature(GenEngine.__init__).parameters
    attrs = {k: kw.pop(k) for k in list(kw) if k not in taken}
    eng = GenEngine(cfg, params=params, **kw)
    # a shared prefix is worth what a retained one is, unless a test says
    attrs.setdefault("share_min_tokens", attrs.get(
        "reuse_min_tokens", eng.share_min_tokens))
    for name, value in attrs.items():
        assert hasattr(eng, name), f"GenEngine has no attribute {name!r}"
        setattr(eng, name, value)
    return eng

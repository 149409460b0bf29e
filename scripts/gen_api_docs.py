"""Generate the API reference (docs/api/*.md) by introspection.

Reference equivalent: the Sphinx ``docs/`` tree the upstream project built
over its public API.  Neither sphinx nor pdoc is installable in this
environment, so this walks ``gordo_tpu`` with stdlib ``inspect`` and emits
deterministic markdown — one file per public module, plus an index.

Run via ``make docs`` (CPU-forced: importing the package must never
claim an accelerator).  Output is committed, so the reference is
browsable on any checkout without running anything.
"""

from __future__ import annotations

import importlib
import inspect
import os
import pkgutil
import re
import shutil
import sys

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "docs", "api")

#: modules whose import pulls optional SDKs or is internal plumbing
SKIP = {
    "gordo_tpu.cli.__main__",
}


def _is_public(name: str) -> bool:
    return not name.split(".")[-1].startswith("_")


def _doc(obj) -> str:
    return inspect.getdoc(obj) or ""


def _sig(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # default-value reprs can leak memory addresses (e.g. flax's
    # `<_Sentinel object at 0x7f...>`), which made the "deterministic
    # markdown" churn on every regeneration — scrub them
    return re.sub(r"0x[0-9a-f]+", "0x...", sig)


def _first_line(doc: str) -> str:
    return doc.split("\n", 1)[0] if doc else ""


def _document_class(cls, lines: list) -> None:
    bases = ", ".join(
        b.__name__ for b in cls.__bases__ if b is not object
    )
    head = f"class {cls.__name__}{f'({bases})' if bases else ''}"
    lines.append(f"### `{head}`\n")
    doc = _doc(cls)
    if doc:
        lines.append(doc + "\n")
    try:
        init = cls.__init__
        if init is not object.__init__:
            lines.append(f"```python\n{cls.__name__}{_sig(init)}\n```\n")
    except Exception:
        pass
    members = []
    for mname, member in sorted(vars(cls).items()):
        if mname.startswith("_"):
            continue
        if isinstance(member, (staticmethod, classmethod)):
            member = member.__func__
        if inspect.isfunction(member):
            members.append((mname, member, "method"))
        elif isinstance(member, property):
            members.append((mname, member, "property"))
    for mname, member, kind in members:
        if kind == "property":
            lines.append(f"- **`.{mname}`** *(property)* — "
                         f"{_first_line(_doc(member.fget) if member.fget else '')}")
        else:
            lines.append(f"- **`.{mname}{_sig(member)}`** — "
                         f"{_first_line(_doc(member))}")
    if members:
        lines.append("")


def _document_function(fn, lines: list) -> None:
    lines.append(f"### `{fn.__name__}{_sig(fn)}`\n")
    doc = _doc(fn)
    if doc:
        lines.append(doc + "\n")


def _document_module(modname: str) -> "tuple[str, str] | None":
    try:
        mod = importlib.import_module(modname)
    except Exception as exc:  # import-gated optional deps stay listed
        return (f"# `{modname}`\n\n*Import requires an optional dependency "
                f"not present in this environment:* `{exc}`\n", "")
    lines = [f"# `{modname}`\n"]
    moddoc = _doc(mod)
    if moddoc:
        lines.append(moddoc + "\n")

    classes, functions = [], []
    for name, obj in sorted(vars(mod).items()):
        if name.startswith("_"):
            continue
        if inspect.isclass(obj) and obj.__module__ == modname:
            classes.append(obj)
        elif inspect.isfunction(obj) and obj.__module__ == modname:
            functions.append(obj)
    if classes:
        lines.append("## Classes\n")
        for cls in classes:
            _document_class(cls, lines)
    if functions:
        lines.append("## Functions\n")
        for fn in functions:
            _document_function(fn, lines)
    if not classes and not functions and not moddoc:
        return None
    return "\n".join(lines) + "\n", _first_line(moddoc)


def main() -> int:
    import gordo_tpu

    out_dir = os.path.abspath(OUT_DIR)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)

    modnames = ["gordo_tpu"]
    pkg_path = gordo_tpu.__path__
    for info in pkgutil.walk_packages(pkg_path, prefix="gordo_tpu."):
        modnames.append(info.name)
    modnames = sorted(
        n for n in modnames
        if n not in SKIP and all(_is_public(p) for p in n.split("."))
    )

    index = [
        "# gordo_tpu API reference\n",
        "Generated by `make docs` (`scripts/gen_api_docs.py`) — stdlib-"
        "introspection markdown, one page per public module.\n",
    ]
    n_pages = 0
    for modname in modnames:
        result = _document_module(modname)
        if result is None:
            continue
        text, summary = result
        fname = modname.replace(".", "_") + ".md"
        with open(os.path.join(out_dir, fname), "w") as fh:
            fh.write(text)
        index.append(f"- [`{modname}`]({fname})" + (f" — {summary}" if summary else ""))
        n_pages += 1
    with open(os.path.join(out_dir, "index.md"), "w") as fh:
        fh.write("\n".join(index) + "\n")
    print(f"API reference: {n_pages} module pages in {out_dir}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Generate the operator API reference from the live op registry.

Reference role: the reference auto-generates its op docs from the
``DMLC_DECLARE_PARAMETER`` metadata (``__FIELDS__``) that every operator
registers — ``docs/api/python/ndarray.md`` and every language binding's
op reference come from that single source (SURVEY.md §5 config tiers).
Here the single source is ``mxnet_tpu/ops/registry.py``: each OpDef
carries arguments/outputs/aux names, typed AttrSpecs (parser, default,
required, doc) and the op docstring.  This tool walks the registry and
emits ``docs/api/ops.md``.

Usage::

    python tools/docgen.py [--out docs/api/ops.md] [--check]

``--check`` exits nonzero if the file on disk is stale (CI hook).
"""
from __future__ import annotations

import argparse
import io
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# doc generation is platform-independent and must never touch an
# accelerator backend: pin the CPU platform before jax is imported
os.environ["JAX_PLATFORMS"] = "cpu"


def _spec_type_name(spec):
    """Human name for an AttrSpec's parser."""
    fn = spec.parse
    name = getattr(fn, "__name__", "str")
    return {
        "int": "int", "float": "float", "str": "string",
        "_parse_bool": "bool", "_parse_shape": "shape",
        "_parse_int_or_none": "int or None",
        "_parse_float_tuple": "float tuple", "_parse_dtype": "dtype",
    }.get(name, name.lstrip("_") or "value")


def _names(val, attrs_hint="(depends on attrs)"):
    """Static name list, or a marker for attr-dependent structure."""
    if callable(val):
        return attrs_hint
    return ", ".join(val) if val else "(none)"


def generate():
    import mxnet_tpu  # noqa: F401  (registers every op)
    from mxnet_tpu.ops import registry

    # group aliases: several names can map to one OpDef
    by_def = {}
    for name in registry.list_ops():
        op = registry.get_op(name)
        by_def.setdefault(id(op), {"op": op, "names": []})["names"] \
            .append(name)

    out = io.StringIO()
    out.write("# Operator reference\n\n")
    out.write("<!-- GENERATED by tools/docgen.py from the op registry; "
              "do not edit by hand. -->\n\n")
    n_ops = len(registry.list_ops())
    out.write("%d registered operator names (%d distinct operators; "
              "the rest are aliases).\n" % (n_ops, len(by_def)))

    for rec in sorted(by_def.values(), key=lambda r: r["names"][0]):
        op = rec["op"]
        names = sorted(rec["names"], key=lambda n: (n != op.name, n))
        out.write("\n---\n\n## %s\n\n" % names[0])
        if len(names) > 1:
            out.write("*Aliases: %s*\n\n"
                      % ", ".join("`%s`" % n for n in names[1:]))
        if op.doc:
            out.write(op.doc.strip() + "\n\n")
        out.write("**Inputs**: %s" % _names(op._arguments))
        if op.key_var_num_args:
            out.write(" (variadic; count from `%s`)"
                      % op.key_var_num_args)
        out.write("  \n**Outputs**: %s" % _names(op._outputs))
        aux = _names(op._aux_states, "(depends on attrs)")
        if aux != "(none)":
            out.write("  \n**Auxiliary states**: %s" % aux)
        flags = []
        if op.stateful:
            flags.append("stateful")
        if op.needs_rng:
            flags.append("uses RNG%s" % (" (at inference too)"
                                         if op.rng_at_eval else ""))
        if op.mutate:
            flags.append("mutates inputs %s"
                         % list({a for _, a in op.mutate}))
        if op.free_attrs:
            flags.append("accepts free-form attributes")
        if flags:
            out.write("  \n**Behavior**: %s" % "; ".join(flags))
        out.write("\n")
        if op.attr_specs:
            out.write("\n| attribute | type | default | required "
                      "| doc |\n|---|---|---|---|---|\n")
            for k in sorted(op.attr_specs):
                spec = op.attr_specs[k]
                default = "" if spec.required else repr(spec.default)
                out.write("| `%s` | %s | %s | %s | %s |\n"
                          % (k, _spec_type_name(spec), default,
                             "yes" if spec.required else "",
                             (spec.doc or "").replace("|", "\\|")))
    return out.getvalue()


def main(argv=None):
    parser = argparse.ArgumentParser(description="op reference docgen")
    parser.add_argument("--out",
                        default=os.path.join(REPO, "docs", "api",
                                             "ops.md"))
    parser.add_argument("--check", action="store_true",
                        help="fail if the on-disk file is stale")
    args = parser.parse_args(argv)

    from genutil import sync_file
    text = generate()
    stale = sync_file(args.out, text, args.check)
    if args.check:
        if stale:
            print("STALE: %s does not match the registry; rerun "
                  "tools/docgen.py" % args.out)
            return 1
        print("ok: %s is current" % args.out)
        return 0
    print("wrote %s (%d bytes)" % (args.out, len(text)))
    return 0


if __name__ == "__main__":
    sys.exit(main())

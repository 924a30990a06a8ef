"""Generate the typed C++ operator layer (cpp-package/include/mxt_op.h)
from the live op registry.

Reference role: ``cpp-package/src/OpWrapperGenerator/OpWrapperGenerator.py``
generates ``include/mxnet-cpp/op.h`` — one typed C++ function per
operator, from the same registration metadata that feeds the python
bindings — so C++ users compose ``FullyConnected(data, 64)`` instead of
stringly-typed kwargs.  Here the single source is
``mxnet_tpu/ops/registry.py`` and the emitted functions sit on top of
the ``MXTSymbolCompose`` C ABI (``mxt_api.h``).

Typing rules:
- required attrs are plain typed parameters (int / double / bool /
  std::string / mxt::Shape / std::vector<double>);
- optional attrs are ``std::optional<T>`` defaulting to ``std::nullopt``
  ("use the registry default" — the registry stays the single source of
  default values, nothing is duplicated into C++);
- ops with a fixed input list get one ``Symbol`` parameter per input,
  trailing parameter inputs defaulted to a null Symbol (auto-created by
  the compose glue, reference auto-wiring);
- ops whose input list depends on attrs (Convolution's no_bias, RNN
  mode) get ``data`` plus an ``extra_inputs`` vector;
- variadic ops (Concat) take ``std::vector<Symbol>`` and the count attr
  is filled automatically.

Usage::

    python tools/gen_cpp_ops.py [--check]
"""
from __future__ import annotations

import argparse
import io
import keyword
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# doc generation is platform-independent and must never touch an
# accelerator backend: pin the CPU platform before jax is imported
os.environ["JAX_PLATFORMS"] = "cpu"

OUT = os.path.join(REPO, "cpp-package", "include", "mxt_op.h")

CPP_KEYWORDS = {
    "auto", "bool", "break", "case", "catch", "char", "class", "const",
    "continue", "default", "delete", "do", "double", "else", "enum",
    "explicit", "export", "extern", "false", "float", "for", "friend",
    "goto", "if", "inline", "int", "long", "mutable", "namespace", "new",
    "operator", "private", "protected", "public", "register", "return",
    "short", "signed", "sizeof", "static", "struct", "switch", "template",
    "this", "throw", "true", "try", "typedef", "typeid", "typename",
    "union", "unsigned", "using", "virtual", "void", "volatile", "while",
}

# parse-fn name -> (C++ type, pass-by, setter suffix)
TYPE_MAP = {
    "int": ("int", "value"),
    "float": ("double", "value"),
    "_parse_bool": ("bool", "value"),
    "str": ("std::string", "cref"),
    "_parse_dtype": ("std::string", "cref"),
    "_parse_shape": ("mxt::Shape", "cref"),
    "_parse_float_tuple": ("std::vector<double>", "cref"),
    "_parse_int_or_none": ("int", "value"),
}


def _cpp_ident(name):
    out = "".join(c if (c.isalnum() or c == "_") else "_" for c in name)
    if out[0].isdigit():
        out = "_" + out
    if out in CPP_KEYWORDS or keyword.iskeyword(out):
        out += "_"
    return out


def _attr_type(spec):
    nm = getattr(spec.parse, "__name__", "str")
    return TYPE_MAP.get(nm, ("std::string", "cref"))


def _param_decl(cpp_type, by, pname, optional):
    if optional:
        return "std::optional<%s> %s = std::nullopt" % (cpp_type, pname)
    if by == "cref":
        return "const %s &%s" % (cpp_type, pname)
    return "%s %s" % (cpp_type, pname)


PROLOGUE = r'''/*
 * mxt_op.h — typed C++ operator layer over the mxt_api C ABI.
 *
 * GENERATED by tools/gen_cpp_ops.py from the op registry; do not edit
 * by hand.  Role of the reference's include/mxnet-cpp/op.h (emitted by
 * OpWrapperGenerator.py).  Requires C++17 (std::optional).
 *
 * Every function has two overloads: with an explicit node name, and
 * auto-named.  Optional attributes default to std::nullopt, meaning
 * "use the op registry's default" — defaults live in one place only.
 */
#ifndef MXT_OP_H_
#define MXT_OP_H_

#include <cstdio>
#include <memory>
#include <optional>
#include <stdexcept>
#include <string>
#include <vector>

#include "mxt_api.h"

namespace mxt {

using Shape = std::vector<int64_t>;

class Symbol {
 public:
  Symbol() : h_(nullptr) {}
  explicit Symbol(MXTHandle h)
      : h_(new MXTHandle(h), [](MXTHandle *p) {
          MXTFree(*p);
          delete p;
        }) {}

  static Symbol Variable(const std::string &name) {
    MXTHandle out = 0;
    if (MXTSymbolVariable(name.c_str(), &out) != 0)
      throw std::runtime_error(MXTGetLastError());
    return Symbol(out);
  }

  MXTHandle handle() const { return h_ ? *h_ : 0; }
  explicit operator bool() const { return h_ != nullptr; }

 private:
  std::shared_ptr<MXTHandle> h_;
};

namespace detail {

class AttrMap {
 public:
  void set(const std::string &k, int v) { put(k, std::to_string(v)); }
  void set(const std::string &k, int64_t v) { put(k, std::to_string(v)); }
  void set(const std::string &k, bool v) { put(k, v ? "True" : "False"); }
  void set(const std::string &k, double v) {
    char buf[48];
    std::snprintf(buf, sizeof(buf), "%.17g", v);
    put(k, buf);
  }
  void set(const std::string &k, const std::string &v) { put(k, v); }
  void set(const std::string &k, const char *v) { put(k, v); }
  void set(const std::string &k, const Shape &v) {
    put(k, join(v.begin(), v.end()));
  }
  void set(const std::string &k, const std::vector<double> &v) {
    std::string s = "(";
    for (size_t i = 0; i < v.size(); ++i) {
      char buf[48];
      std::snprintf(buf, sizeof(buf), "%.17g", v[i]);
      s += buf;
      if (i + 1 < v.size() || v.size() == 1) s += ",";
    }
    put(k, s + ")");
  }
  template <typename T>
  void set(const std::string &k, const std::optional<T> &v) {
    if (v) set(k, *v);
  }

  std::vector<const char *> keys() const {
    std::vector<const char *> out;
    for (const auto &kv : kv_) out.push_back(kv.first.c_str());
    return out;
  }
  std::vector<const char *> vals() const {
    std::vector<const char *> out;
    for (const auto &kv : kv_) out.push_back(kv.second.c_str());
    return out;
  }
  int size() const { return static_cast<int>(kv_.size()); }
  bool has(const std::string &k) const {
    for (const auto &kv : kv_)
      if (kv.first == k) return true;
    return false;
  }

 private:
  void put(const std::string &k, std::string v) {
    kv_.emplace_back(k, std::move(v));
  }

  template <typename It>
  static std::string join(It begin, It end) {
    std::string s = "(";
    size_t n = 0;
    for (It it = begin; it != end; ++it, ++n) {
      s += std::to_string(*it);
      s += ",";
    }
    if (n > 1) s.pop_back();  // trailing comma only for 0/1 elements
    return s + ")";
  }
  // insertion-ordered; op attr counts are tiny
  std::vector<std::pair<std::string, std::string>> kv_;
};

inline Symbol Compose(const char *op, const std::string &name,
                      const std::vector<Symbol> &inputs,
                      const AttrMap &attrs) {
  std::vector<MXTHandle> ins;
  for (size_t i = 0; i < inputs.size(); ++i) {
    if (!inputs[i]) {
      // a trailing null Symbol means "auto-create this input"; a null
      // BEFORE a provided input would silently shift positions
      for (size_t j = i + 1; j < inputs.size(); ++j)
        if (inputs[j])
          throw std::invalid_argument(
              std::string(op) + ": input " + std::to_string(j) +
              " given but an earlier input is null");
      break;
    }
    ins.push_back(inputs[i].handle());
  }
  MXTHandle out = 0;
  auto ks = attrs.keys();
  auto vs = attrs.vals();
  if (MXTSymbolCompose(op, name.empty() ? nullptr : name.c_str(),
                       ins.data(), static_cast<int>(ins.size()),
                       ks.data(), vs.data(), attrs.size(), &out) != 0)
    throw std::runtime_error(MXTGetLastError());
  return Symbol(out);
}

}  // namespace detail

/* Escape hatch: compose any registered op by name. */
inline Symbol Op(const std::string &op_name,
                 const std::vector<Symbol> &inputs,
                 const std::vector<std::pair<std::string, std::string>>
                     &attrs = {},
                 const std::string &name = "") {
  detail::AttrMap a;
  for (const auto &kv : attrs) a.set(kv.first, kv.second);
  return detail::Compose(op_name.c_str(), name, inputs, a);
}

/* Arithmetic sugar (reference mxnet-cpp Symbol operators). */
inline Symbol operator+(const Symbol &a, const Symbol &b) {
  return detail::Compose("_plus", "", {a, b}, detail::AttrMap());
}
inline Symbol operator-(const Symbol &a, const Symbol &b) {
  return detail::Compose("_minus", "", {a, b}, detail::AttrMap());
}
inline Symbol operator*(const Symbol &a, const Symbol &b) {
  return detail::Compose("_mul", "", {a, b}, detail::AttrMap());
}
inline Symbol operator/(const Symbol &a, const Symbol &b) {
  return detail::Compose("_div", "", {a, b}, detail::AttrMap());
}
inline Symbol operator+(const Symbol &a, double s) {
  detail::AttrMap m;
  m.set("scalar", s);
  return detail::Compose("_plus_scalar", "", {a}, m);
}
inline Symbol operator-(const Symbol &a, double s) {
  detail::AttrMap m;
  m.set("scalar", s);
  return detail::Compose("_minus_scalar", "", {a}, m);
}
inline Symbol operator*(const Symbol &a, double s) {
  detail::AttrMap m;
  m.set("scalar", s);
  return detail::Compose("_mul_scalar", "", {a}, m);
}
inline Symbol operator/(const Symbol &a, double s) {
  detail::AttrMap m;
  m.set("scalar", s);
  return detail::Compose("_div_scalar", "", {a}, m);
}

'''

EPILOGUE = '''
}  // namespace mxt

#endif  // MXT_OP_H_
'''


def _emit_op(out, op):
    fn = _cpp_ident(op.name)
    doc = (op.doc or "").strip().replace("*/", "*\\/")
    doc = doc.replace("\n", "\n * ")
    out.write("/** %s */\n" % (doc or op.name))

    # attr parameters: required first, then optionals (sorted for
    # deterministic output)
    req = sorted(k for k, s in op.attr_specs.items()
                 if s.required and k != op.key_var_num_args)
    opt = sorted(k for k, s in op.attr_specs.items()
                 if not s.required and k != op.key_var_num_args)

    attr_params, attr_sets = [], []
    for k in req + opt:
        spec = op.attr_specs[k]
        cpp_type, by = _attr_type(spec)
        pname = _cpp_ident(k)
        optional = not spec.required
        attr_params.append(_param_decl(cpp_type, by, pname, optional))
        attr_sets.append('  attrs_.set("%s", %s);\n' % (k, pname))

    variadic = bool(op.key_var_num_args)
    args = op._arguments
    # Parameter order (C++: no non-default after a defaulted param):
    #   name, non-default inputs, required attrs, defaulted inputs,
    #   optional attrs.
    if variadic:
        lead_inputs = [("const std::vector<Symbol> &data", "data")]
        tail_inputs = []
    elif callable(args):
        # input list depends on attrs: expose data + an explicit extras
        # vector (auto-wiring creates whatever is omitted)
        lead_inputs = [("Symbol data", "data")]
        tail_inputs = [("const std::vector<Symbol> &extra_inputs = {}",
                        "extra_inputs")]
    else:
        names = [_cpp_ident(a) for a in args]
        lead_inputs = [("Symbol %s" % names[0], names[0])] if names else []
        # trailing parameter inputs are auto-created when omitted
        tail_inputs = [("Symbol %s = Symbol()" % a, a) for a in names[1:]]

    req_params = [p for k, p in zip(req + opt, attr_params)
                  if op.attr_specs[k].required]
    opt_params = [p for k, p in zip(req + opt, attr_params)
                  if not op.attr_specs[k].required]
    params = (["const std::string &name"] + [d for d, _ in lead_inputs] +
              req_params + [d for d, _ in tail_inputs] + opt_params)

    out.write("inline Symbol %s(%s) {\n" % (fn, ", ".join(params)))
    out.write("  detail::AttrMap attrs_;\n")
    for k, setline in zip(req + opt, attr_sets):
        out.write(setline)
    if variadic:
        out.write('  attrs_.set("%s", '
                  "static_cast<int64_t>(data.size()));\n"
                  % op.key_var_num_args)
        out.write('  return detail::Compose("%s", name, data, attrs_);\n'
                  % op.name)
    elif callable(args):
        out.write("  std::vector<Symbol> ins;\n"
                  "  ins.push_back(data);\n"
                  "  for (const auto &s : extra_inputs) ins.push_back(s);\n")
        out.write('  return detail::Compose("%s", name, ins, attrs_);\n'
                  % op.name)
    else:
        in_expr = "{%s}" % ", ".join(n for _, n in
                                     lead_inputs + tail_inputs)
        out.write('  return detail::Compose("%s", name, %s, attrs_);\n'
                  % (op.name, in_expr))
    out.write("}\n\n")

    # auto-named overload: same signature minus `name`, forwarding ""
    fwd_args = []
    for _, n in lead_inputs:
        fwd_args.append(n)
    for k in req:
        fwd_args.append(_cpp_ident(k))
    for _, n in tail_inputs:
        fwd_args.append(n)
    for k in opt:
        fwd_args.append(_cpp_ident(k))
    params_noname = ([d for d, _ in lead_inputs] + req_params +
                     [d for d, _ in tail_inputs] + opt_params)
    out.write("inline Symbol %s(%s) {\n  return %s(\"\"%s%s);\n}\n\n"
              % (fn, ", ".join(params_noname), fn,
                 ", " if fwd_args else "", ", ".join(fwd_args)))


def generate():
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.ops import registry

    seen = set()
    ops = []
    for name in sorted(registry.list_ops()):
        op = registry.get_op(name)
        if id(op) in seen:
            continue
        seen.add(id(op))
        if op.free_attrs:
            continue  # Custom: untypable, use mxt::Op
        if op.name.startswith("_") and not op.name.startswith("_contrib_"):
            continue  # internal ops reachable via mxt::Op / operators
        ops.append(op)

    out = io.StringIO()
    out.write(PROLOGUE)
    out.write("/* %d typed operators (registry has %d names incl. "
              "aliases and internal ops;\n * internal ops are reachable "
              "via mxt::Op). */\n\n"
              % (len(ops), len(registry.list_ops())))
    for op in sorted(ops, key=lambda o: o.name):
        _emit_op(out, op)
    out.write(EPILOGUE)
    return out.getvalue()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from genutil import sync_file
    text = generate()
    stale = sync_file(OUT, text, args.check)
    if args.check:
        if stale:
            print("STALE: %s; rerun tools/gen_cpp_ops.py" % OUT)
            return 1
        print("ok: %s is current" % OUT)
        return 0
    print("wrote %s (%d bytes)" % (OUT, len(text)))
    return 0


if __name__ == "__main__":
    sys.exit(main())

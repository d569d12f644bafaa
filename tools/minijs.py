"""minijs — a small ES2017-subset interpreter, enough to EXECUTE the web
client (web/*.js) in CI.

Why this exists: the image ships no JS runtime (no node/deno/quickjs, no
embeddable engine package), and an early review flagged that the client
tests only regexed the source. This module parses and tree-walks the
actual client files against Python-implemented DOM/WebCodecs stubs
(tests/web_stubs.py), so the demux, ACK, input-mapping and dashboard
logic run for real under pytest.

Supported subset (scoped to what web/*.js uses — see tests):
  let/const/var, functions, arrow functions, default+rest params, array/
  object destructuring, classes (methods, static methods/fields, instance
  fields), template literals, regex literals, for/for-of/for-in, while,
  do-while, switch, try/catch/finally, throw, spread in calls/arrays,
  optional chaining, ?? and ||= style compound assignment, typeof/in/
  instanceof/delete, async/await (eager promises + a microtask queue),
  Map/Set, typed arrays (Uint8Array/Int16Array/Float32Array/DataView/
  ArrayBuffer), JSON, Math, String/Array/Object builtins, btoa/atob.

Deliberately NOT supported: prototype mutation, getters/setters, labels,
generators, `with`, eval, symbols, proxies.
"""

from __future__ import annotations

import json as _json
import math as _math
import re as _re
import struct as _struct
from typing import Any, Callable, Dict, List, Optional, Tuple

# ============================================================= lexer

KEYWORDS = {
    "var", "let", "const", "function", "return", "if", "else", "for", "of",
    "in", "while", "do", "break", "continue", "new", "delete", "typeof",
    "instanceof", "this", "null", "undefined", "true", "false", "class",
    "static", "throw", "try", "catch", "finally", "switch", "case",
    "default", "async", "await", "void",
}

PUNCT = [
    "?.", "...", "===", "!==", "**=", "<<=", ">>=", ">>>=", ">>>", "&&=",
    "||=", "??=", "==", "!=", "<=", ">=", "&&", "||", "??", "++", "--",
    "+=", "-=", "*=", "/=", "%=", "&=", "|=", "^=", "=>", "<<", ">>", "**",
    "{", "}", "(", ")", "[", "]", ";", ",", "<", ">", "+", "-", "*", "/",
    "%", "&", "|", "^", "!", "~", "?", ":", "=", ".",
]


class Tok:
    __slots__ = ("kind", "value", "line")

    def __init__(self, kind: str, value: Any, line: int):
        self.kind = kind        # num str tmpl regex ident kw punct eof
        self.value = value
        self.line = line

    def __repr__(self):
        return f"Tok({self.kind},{self.value!r})"


class LexError(SyntaxError):
    pass


def tokenize(src: str) -> List[Tok]:
    toks: List[Tok] = []
    i = 0
    n = len(src)
    line = 1

    def prev_allows_regex() -> bool:
        """A '/' starts a regex (not division) after operators/keywords."""
        for t in reversed(toks):
            if t.kind in ("num", "str", "tmpl", "regex"):
                return False
            if t.kind == "ident":
                return False
            if t.kind == "kw":
                return t.value not in ("this", "null", "true", "false",
                                       "undefined")
            if t.kind == "punct":
                return t.value not in (")", "]", "}", "++", "--")
            return True
        return True

    while i < n:
        c = src[i]
        if c == "\n":
            line += 1
            i += 1
            continue
        if c in " \t\r":
            i += 1
            continue
        if src.startswith("//", i):
            j = src.find("\n", i)
            i = n if j < 0 else j
            continue
        if src.startswith("/*", i):
            j = src.find("*/", i)
            if j < 0:
                raise LexError(f"unterminated comment at line {line}")
            line += src.count("\n", i, j)
            i = j + 2
            continue
        if c == "`":
            # template literal: list of ('s', str) / ('e', token-list) parts
            i += 1
            parts: List[Tuple[str, Any]] = []
            buf = []
            while i < n:
                ch = src[i]
                if ch == "`":
                    i += 1
                    break
                if ch == "\\":
                    esc, i2 = _read_escape(src, i, line)
                    buf.append(esc)
                    i = i2
                    continue
                if src.startswith("${", i):
                    if buf:
                        parts.append(("s", "".join(buf)))
                        buf = []
                    depth = 1
                    j = i + 2
                    while j < n and depth:
                        if src[j] == "{":
                            depth += 1
                        elif src[j] == "}":
                            depth -= 1
                        elif src[j] in "\"'`":
                            j = _skip_string(src, j, line)
                            continue
                        j += 1
                    sub = src[i + 2:j - 1]
                    parts.append(("e", tokenize(sub)))
                    line += src.count("\n", i, j)
                    i = j
                    continue
                if ch == "\n":
                    line += 1
                buf.append(ch)
                i += 1
            else:
                raise LexError(f"unterminated template at line {line}")
            if buf:
                parts.append(("s", "".join(buf)))
            toks.append(Tok("tmpl", parts, line))
            continue
        if c in "\"'":
            quote = c
            i += 1
            buf = []
            while i < n and src[i] != quote:
                if src[i] == "\\":
                    esc, i = _read_escape(src, i, line)
                    buf.append(esc)
                else:
                    if src[i] == "\n":
                        raise LexError(f"newline in string at line {line}")
                    buf.append(src[i])
                    i += 1
            if i >= n:
                raise LexError(f"unterminated string at line {line}")
            i += 1
            toks.append(Tok("str", "".join(buf), line))
            continue
        if c.isdigit() or (c == "." and i + 1 < n and src[i + 1].isdigit()):
            m = _re.match(
                r"0[xX][0-9a-fA-F]+|0[bB][01]+|0[oO][0-7]+|"
                r"\d+\.?\d*(?:[eE][+-]?\d+)?|\.\d+(?:[eE][+-]?\d+)?",
                src[i:])
            text = m.group(0)
            if text[:2].lower() == "0x":
                val = float(int(text, 16))
            elif text[:2].lower() == "0b":
                val = float(int(text, 2))
            elif text[:2].lower() == "0o":
                val = float(int(text, 8))
            else:
                val = float(text)
            toks.append(Tok("num", val, line))
            i += len(text)
            continue
        if c.isalpha() or c in "_$":
            m = _re.match(r"[A-Za-z_$][A-Za-z0-9_$]*", src[i:])
            word = m.group(0)
            toks.append(Tok("kw" if word in KEYWORDS else "ident",
                            word, line))
            i += len(word)
            continue
        if c == "/" and prev_allows_regex():
            j = i + 1
            in_class = False
            while j < n:
                ch = src[j]
                if ch == "\\":
                    j += 2
                    continue
                if ch == "[":
                    in_class = True
                elif ch == "]":
                    in_class = False
                elif ch == "/" and not in_class:
                    break
                elif ch == "\n":
                    raise LexError(f"unterminated regex at line {line}")
                j += 1
            pattern = src[i + 1:j]
            j += 1
            fm = _re.match(r"[a-z]*", src[j:])
            flags = fm.group(0)
            toks.append(Tok("regex", (pattern, flags), line))
            i = j + len(flags)
            continue
        for p in PUNCT:
            if src.startswith(p, i):
                toks.append(Tok("punct", p, line))
                i += len(p)
                break
        else:
            raise LexError(f"unexpected char {c!r} at line {line}")
    toks.append(Tok("eof", None, line))
    return toks


def _read_escape(src: str, i: int, line: int) -> Tuple[str, int]:
    """i points at the backslash; returns (char, next_i)."""
    c = src[i + 1]
    simple = {"n": "\n", "t": "\t", "r": "\r", "b": "\b", "f": "\f",
              "v": "\v", "0": "\0", "\n": ""}
    if c in simple:
        return simple[c], i + 2
    if c == "x":
        return chr(int(src[i + 2:i + 4], 16)), i + 4
    if c == "u":
        if src[i + 2] == "{":
            j = src.index("}", i)
            return chr(int(src[i + 3:j], 16)), j + 1
        return chr(int(src[i + 2:i + 6], 16)), i + 6
    return c, i + 2


def _skip_string(src: str, i: int, line: int) -> int:
    quote = src[i]
    i += 1
    while i < len(src) and src[i] != quote:
        if src[i] == "\\":
            i += 1
        i += 1
    return i + 1


# ============================================================= parser

ASSIGN_OPS = {"=", "+=", "-=", "*=", "/=", "%=", "&=", "|=", "^=", "<<=",
              ">>=", ">>>=", "**=", "&&=", "||=", "??="}

BIN_PREC = {
    "??": 1, "||": 2, "&&": 3, "|": 4, "^": 5, "&": 6,
    "==": 7, "!=": 7, "===": 7, "!==": 7,
    "<": 8, ">": 8, "<=": 8, ">=": 8, "in": 8, "instanceof": 8,
    "<<": 9, ">>": 9, ">>>": 9,
    "+": 10, "-": 10,
    "*": 11, "/": 11, "%": 11,
    "**": 12,
}


class Parser:
    def __init__(self, toks: List[Tok]):
        self.toks = toks
        self.i = 0

    # ---- helpers

    def peek(self, k: int = 0) -> Tok:
        return self.toks[min(self.i + k, len(self.toks) - 1)]

    def next(self) -> Tok:
        t = self.toks[self.i]
        self.i += 1
        return t

    def at(self, kind: str, value: Any = None) -> bool:
        t = self.peek()
        return t.kind == kind and (value is None or t.value == value)

    def eat(self, kind: str, value: Any = None) -> Optional[Tok]:
        if self.at(kind, value):
            return self.next()
        return None

    def expect(self, kind: str, value: Any = None) -> Tok:
        t = self.next()
        if t.kind != kind or (value is not None and t.value != value):
            raise SyntaxError(
                f"expected {value or kind}, got {t.kind} {t.value!r} "
                f"at line {t.line}")
        return t

    def semi(self) -> None:
        self.eat("punct", ";")

    # ---- program

    def parse_program(self) -> list:
        stmts = []
        while not self.at("eof"):
            stmts.append(self.statement())
        return stmts

    # ---- statements

    def statement(self):
        t = self.peek()
        if t.kind == "punct" and t.value == "{":
            return self.block()
        if t.kind == "punct" and t.value == ";":
            self.next()
            return ("empty",)
        if t.kind == "kw":
            v = t.value
            if v in ("var", "let", "const"):
                d = self.var_decl()
                self.semi()
                return d
            if v == "function":
                return self.func_decl(False)
            if v == "async" and self.peek(1).kind == "kw" \
                    and self.peek(1).value == "function":
                self.next()
                return self.func_decl(True)
            if v == "class":
                return self.class_decl()
            if v == "if":
                return self.if_stmt()
            if v == "for":
                return self.for_stmt()
            if v == "while":
                self.next()
                self.expect("punct", "(")
                test = self.expression()
                self.expect("punct", ")")
                return ("while", test, self.statement())
            if v == "do":
                self.next()
                body = self.statement()
                self.expect("kw", "while")
                self.expect("punct", "(")
                test = self.expression()
                self.expect("punct", ")")
                self.semi()
                return ("dowhile", body, test)
            if v == "return":
                self.next()
                if self.at("punct", ";") or self.at("punct", "}") \
                        or self.at("eof"):
                    self.semi()
                    return ("ret", None)
                e = self.expression()
                self.semi()
                return ("ret", e)
            if v == "break":
                self.next()
                self.semi()
                return ("break",)
            if v == "continue":
                self.next()
                self.semi()
                return ("continue",)
            if v == "throw":
                self.next()
                e = self.expression()
                self.semi()
                return ("throw", e)
            if v == "try":
                return self.try_stmt()
            if v == "switch":
                return self.switch_stmt()
        e = self.expression()
        self.semi()
        return ("expr", e)

    def block(self):
        self.expect("punct", "{")
        stmts = []
        while not self.at("punct", "}"):
            stmts.append(self.statement())
        self.expect("punct", "}")
        return ("block", stmts)

    def var_decl(self):
        kind = self.next().value
        decls = []
        while True:
            target = self.binding_target()
            init = None
            if self.eat("punct", "="):
                init = self.assignment()
            decls.append((target, init))
            if not self.eat("punct", ","):
                break
        return ("var", kind, decls)

    def binding_target(self):
        if self.at("punct", "["):
            self.next()
            elems = []
            while not self.at("punct", "]"):
                if self.eat("punct", ","):
                    elems.append(None)
                    continue
                pat = self.binding_target()
                default = None
                if self.eat("punct", "="):
                    default = self.assignment()
                elems.append(("el", pat, default))
                if not self.at("punct", "]"):
                    self.expect("punct", ",")
            self.expect("punct", "]")
            return ("arrpat", elems)
        if self.at("punct", "{"):
            self.next()
            props = []
            while not self.at("punct", "}"):
                key = self.next()
                if key.kind not in ("ident", "kw", "str"):
                    raise SyntaxError(f"bad objpat key at line {key.line}")
                name = key.value
                pat = ("ident", name)
                if self.eat("punct", ":"):
                    pat = self.binding_target()
                default = None
                if self.eat("punct", "="):
                    default = self.assignment()
                props.append((name, pat, default))
                if not self.at("punct", "}"):
                    self.expect("punct", ",")
            self.expect("punct", "}")
            return ("objpat", props)
        t = self.next()
        if t.kind not in ("ident", "kw"):
            raise SyntaxError(f"bad binding at line {t.line}")
        return ("ident", t.value)

    def func_decl(self, is_async: bool):
        self.expect("kw", "function")
        name = self.expect("ident").value
        params = self.param_list()
        body = self.block()
        return ("func", name, params, body, is_async)

    def param_list(self):
        self.expect("punct", "(")
        params = []
        while not self.at("punct", ")"):
            if self.eat("punct", "..."):
                params.append(("rest", self.expect("ident").value))
            else:
                pat = self.binding_target()
                default = None
                if self.eat("punct", "="):
                    default = self.assignment()
                params.append(("p", pat, default))
            if not self.at("punct", ")"):
                self.expect("punct", ",")
        self.expect("punct", ")")
        return params

    def class_decl(self):
        self.expect("kw", "class")
        name = self.expect("ident").value
        parent = None
        if self.at("ident", "extends") or self.at("kw", "extends"):
            self.next()
            parent = self.expression()
        methods = []
        fields = []
        self.expect("punct", "{")
        while not self.at("punct", "}"):
            if self.eat("punct", ";"):
                continue
            is_static = False
            if self.at("kw", "static"):
                self.next()
                is_static = True
            is_async = False
            if self.at("kw", "async") and not (
                    self.peek(1).kind == "punct"
                    and self.peek(1).value in ("(", "=")):
                self.next()
                is_async = True
            t = self.next()
            if t.kind not in ("ident", "kw", "str"):
                raise SyntaxError(f"bad class member at line {t.line}")
            mname = t.value
            if self.at("punct", "("):
                params = self.param_list()
                body = self.block()
                methods.append((is_static, mname, params, body, is_async))
            else:
                init = None
                if self.eat("punct", "="):
                    init = self.assignment()
                self.semi()
                fields.append((is_static, mname, init))
        self.expect("punct", "}")
        return ("class", name, parent, methods, fields)

    def if_stmt(self):
        self.expect("kw", "if")
        self.expect("punct", "(")
        test = self.expression()
        self.expect("punct", ")")
        cons = self.statement()
        alt = None
        if self.eat("kw", "else"):
            alt = self.statement()
        return ("if", test, cons, alt)

    def for_stmt(self):
        self.expect("kw", "for")
        self.expect("punct", "(")
        init = None
        if not self.at("punct", ";"):
            if self.at("kw", "var") or self.at("kw", "let") \
                    or self.at("kw", "const"):
                kind = self.next().value
                target = self.binding_target()
                if self.at("kw", "of"):
                    self.next()
                    it = self.expression()
                    self.expect("punct", ")")
                    return ("forof", kind, target, it, self.statement())
                if self.at("kw", "in"):
                    self.next()
                    obj = self.expression()
                    self.expect("punct", ")")
                    return ("forin", kind, target, obj, self.statement())
                decls = []
                i0 = None
                if self.eat("punct", "="):
                    i0 = self.assignment()
                decls.append((target, i0))
                while self.eat("punct", ","):
                    tgt = self.binding_target()
                    i1 = None
                    if self.eat("punct", "="):
                        i1 = self.assignment()
                    decls.append((tgt, i1))
                init = ("var", kind, decls)
            else:
                e = self.expression()
                if self.at("kw", "of"):
                    self.next()
                    it = self.expression()
                    self.expect("punct", ")")
                    return ("forof", None, _expr_to_pattern(e), it,
                            self.statement())
                if self.at("kw", "in"):
                    self.next()
                    obj = self.expression()
                    self.expect("punct", ")")
                    return ("forin", None, _expr_to_pattern(e), obj,
                            self.statement())
                init = ("expr", e)
        self.expect("punct", ";")
        test = None if self.at("punct", ";") else self.expression()
        self.expect("punct", ";")
        update = None if self.at("punct", ")") else self.expression()
        self.expect("punct", ")")
        return ("for", init, test, update, self.statement())

    def try_stmt(self):
        self.expect("kw", "try")
        block = self.block()
        param = catch = final = None
        if self.eat("kw", "catch"):
            if self.eat("punct", "("):
                param = self.binding_target()
                self.expect("punct", ")")
            catch = self.block()
        if self.eat("kw", "finally"):
            final = self.block()
        return ("try", block, param, catch, final)

    def switch_stmt(self):
        self.expect("kw", "switch")
        self.expect("punct", "(")
        disc = self.expression()
        self.expect("punct", ")")
        self.expect("punct", "{")
        cases = []
        while not self.at("punct", "}"):
            if self.eat("kw", "case"):
                test = self.expression()
                self.expect("punct", ":")
            else:
                self.expect("kw", "default")
                self.expect("punct", ":")
                test = None
            body = []
            while not (self.at("kw", "case") or self.at("kw", "default")
                       or self.at("punct", "}")):
                body.append(self.statement())
            cases.append((test, body))
        self.expect("punct", "}")
        return ("switch", disc, cases)

    # ---- expressions

    def expression(self):
        e = self.assignment()
        if self.at("punct", ","):
            exprs = [e]
            while self.eat("punct", ","):
                exprs.append(self.assignment())
            return ("seq", exprs)
        return e

    def assignment(self):
        if self._arrow_ahead():
            return self.arrow_function(False)
        if self.at("kw", "async") and self._arrow_ahead(1):
            self.next()
            return self.arrow_function(True)
        left = self.conditional()
        t = self.peek()
        if t.kind == "punct" and t.value in ASSIGN_OPS:
            op = self.next().value
            right = self.assignment()
            return ("assign", op, left, right)
        return left

    def _arrow_ahead(self, offset: int = 0) -> bool:
        """Lookahead: identifier=> or (params)=> from position i+offset."""
        t = self.peek(offset)
        if t.kind == "ident" and self.peek(offset + 1).kind == "punct" \
                and self.peek(offset + 1).value == "=>":
            return True
        if t.kind == "punct" and t.value == "(":
            depth = 0
            j = self.i + offset
            while j < len(self.toks):
                tk = self.toks[j]
                if tk.kind == "punct" and tk.value == "(":
                    depth += 1
                elif tk.kind == "punct" and tk.value == ")":
                    depth -= 1
                    if depth == 0:
                        nxt = self.toks[j + 1] if j + 1 < len(self.toks) \
                            else None
                        return (nxt is not None and nxt.kind == "punct"
                                and nxt.value == "=>")
                elif tk.kind == "eof":
                    return False
                j += 1
        return False

    def arrow_function(self, is_async: bool):
        if self.at("ident"):
            params = [("p", ("ident", self.next().value), None)]
        else:
            params = self.param_list()
        self.expect("punct", "=>")
        if self.at("punct", "{"):
            body = self.block()
            expr_body = False
        else:
            body = self.assignment()
            expr_body = True
        return ("fn", None, params, body, is_async, True, expr_body)

    def conditional(self):
        test = self.binary(0)
        if self.at("punct", "?") and not self.at("punct", "?."):
            self.next()
            cons = self.assignment()
            self.expect("punct", ":")
            alt = self.assignment()
            return ("cond", test, cons, alt)
        return test

    def binary(self, min_prec: int):
        left = self.unary()
        while True:
            t = self.peek()
            op = None
            if t.kind == "punct" and t.value in BIN_PREC:
                op = t.value
            elif t.kind == "kw" and t.value in ("in", "instanceof"):
                op = t.value
            if op is None:
                return left
            prec = BIN_PREC[op]
            if prec < min_prec:
                return left
            self.next()
            right = self.binary(prec + 1)
            kind = "logic" if op in ("&&", "||", "??") else "bin"
            left = (kind, op, left, right)

    def unary(self):
        t = self.peek()
        if t.kind == "punct" and t.value in ("!", "-", "+", "~"):
            self.next()
            return ("un", t.value, self.unary())
        if t.kind == "punct" and t.value in ("++", "--"):
            self.next()
            return ("update", t.value, True, self.unary())
        if t.kind == "kw" and t.value in ("typeof", "delete", "void"):
            self.next()
            return ("un", t.value, self.unary())
        if t.kind == "kw" and t.value == "await":
            self.next()
            return ("await", self.unary())
        if t.kind == "kw" and t.value == "new":
            self.next()
            callee = self.member_chain(self.primary(), no_call=True)
            args = []
            if self.at("punct", "("):
                args = self.arguments()
            return self.member_chain(("new", callee, args))
        e = self.postfix()
        return e

    def postfix(self):
        e = self.member_chain(self.primary())
        t = self.peek()
        if t.kind == "punct" and t.value in ("++", "--"):
            self.next()
            return ("update", t.value, False, e)
        return e

    def member_chain(self, e, no_call: bool = False):
        while True:
            if self.at("punct", "."):
                self.next()
                prop = self.next()
                if prop.kind not in ("ident", "kw"):
                    raise SyntaxError(f"bad member at line {prop.line}")
                e = ("member", e, ("str", prop.value), False, False)
            elif self.at("punct", "?."):
                self.next()
                if self.at("punct", "("):
                    e = ("call", e, self.arguments(), True)
                elif self.at("punct", "["):
                    self.next()
                    idx = self.expression()
                    self.expect("punct", "]")
                    e = ("member", e, idx, True, True)
                else:
                    prop = self.next()
                    e = ("member", e, ("str", prop.value), False, True)
            elif self.at("punct", "["):
                self.next()
                idx = self.expression()
                self.expect("punct", "]")
                e = ("member", e, idx, True, False)
            elif self.at("punct", "(") and not no_call:
                e = ("call", e, self.arguments(), False)
            else:
                return e

    def arguments(self):
        self.expect("punct", "(")
        args = []
        while not self.at("punct", ")"):
            if self.eat("punct", "..."):
                args.append(("spread", self.assignment()))
            else:
                args.append(self.assignment())
            if not self.at("punct", ")"):
                self.expect("punct", ",")
        self.expect("punct", ")")
        return args

    def primary(self):
        t = self.next()
        if t.kind == "num":
            return ("num", t.value)
        if t.kind == "str":
            return ("str", t.value)
        if t.kind == "regex":
            return ("regex", t.value[0], t.value[1])
        if t.kind == "tmpl":
            parts = []
            for k, v in t.value:
                if k == "s":
                    parts.append(("s", v))
                else:
                    parts.append(("e", Parser(v).expression()))
            return ("tmpl", parts)
        if t.kind == "ident":
            return ("ident", t.value)
        if t.kind == "kw":
            v = t.value
            if v == "this":
                return ("this",)
            if v == "null":
                return ("null",)
            if v == "undefined":
                return ("undef",)
            if v == "true":
                return ("bool", True)
            if v == "false":
                return ("bool", False)
            if v == "function":
                name = None
                if self.at("ident"):
                    name = self.next().value
                params = self.param_list()
                body = self.block()
                return ("fn", name, params, body, False, False, False)
            if v == "async" and self.at("kw", "function"):
                self.next()
                name = None
                if self.at("ident"):
                    name = self.next().value
                params = self.param_list()
                body = self.block()
                return ("fn", name, params, body, True, False, False)
            if v == "class":
                # anonymous class expression — not used by the client
                raise SyntaxError(f"class expression at line {t.line}")
            if v in ("of", "static", "async", "let"):   # contextual
                return ("ident", v)
            raise SyntaxError(f"unexpected keyword {v} at line {t.line}")
        if t.kind == "punct":
            if t.value == "(":
                e = self.expression()
                self.expect("punct", ")")
                return e
            if t.value == "[":
                elems = []
                while not self.at("punct", "]"):
                    if self.at("punct", ","):
                        self.next()
                        elems.append(("undef",))
                        continue
                    if self.eat("punct", "..."):
                        elems.append(("spread", self.assignment()))
                    else:
                        elems.append(self.assignment())
                    if not self.at("punct", "]"):
                        self.expect("punct", ",")
                self.expect("punct", "]")
                return ("arr", elems)
            if t.value == "{":
                props = []
                while not self.at("punct", "}"):
                    if self.eat("punct", "..."):
                        props.append(("spread", self.assignment()))
                        if not self.at("punct", "}"):
                            self.expect("punct", ",")
                        continue
                    key = self.next()
                    computed = False
                    if key.kind == "punct" and key.value == "[":
                        kexpr = self.assignment()
                        self.expect("punct", "]")
                        computed = True
                    elif key.kind in ("ident", "kw", "str"):
                        kexpr = ("str", key.value)
                    elif key.kind == "num":
                        kexpr = ("str", _num_to_str(key.value))
                    else:
                        raise SyntaxError(
                            f"bad object key at line {key.line}")
                    if self.at("punct", "("):
                        params = self.param_list()
                        body = self.block()
                        props.append(("kv", kexpr, (
                            "fn", None, params, body, False, False, False),
                            computed))
                    elif self.eat("punct", ":"):
                        props.append(("kv", kexpr, self.assignment(),
                                      computed))
                    else:   # shorthand
                        props.append(("kv", kexpr,
                                      ("ident", key.value), False))
                    if not self.at("punct", "}"):
                        self.expect("punct", ",")
                self.expect("punct", "}")
                return ("obj", props)
        raise SyntaxError(f"unexpected token {t.kind} {t.value!r} "
                          f"at line {t.line}")


def _expr_to_pattern(e):
    if e[0] == "ident":
        return e
    if e[0] == "arr":
        return ("arrpat", [("el", _expr_to_pattern(x), None)
                           for x in e[1]])
    raise SyntaxError(f"unsupported for-loop target {e[0]}")


def _num_to_str(v: float) -> str:
    if v != v:
        return "NaN"
    if v == float("inf"):
        return "Infinity"
    if v == float("-inf"):
        return "-Infinity"
    if v == int(v) and abs(v) < 1e21:
        return str(int(v))
    return repr(v)


def parse(src: str) -> list:
    return Parser(tokenize(src)).parse_program()


# ============================================================ runtime

class JSUndefined:
    _inst = None

    def __new__(cls):
        if cls._inst is None:
            cls._inst = super().__new__(cls)
        return cls._inst

    def __repr__(self):
        return "undefined"

    def __bool__(self):
        return False


UNDEF = JSUndefined()


class JSObject:
    def __init__(self, props: Optional[dict] = None, klass=None):
        self.props = props or {}
        self.klass = klass

    def __repr__(self):
        return f"JSObject({list(self.props)[:6]})"


class JSArray:
    def __init__(self, elems: Optional[list] = None):
        self.elems = elems if elems is not None else []

    def __repr__(self):
        return f"JSArray({self.elems!r})"


class JSFunction:
    def __init__(self, name, params, body, env, is_async, is_arrow,
                 expr_body, this_val=UNDEF, interp=None):
        self.name = name or ""
        self.params = params
        self.body = body
        self.env = env
        self.is_async = is_async
        self.is_arrow = is_arrow
        self.expr_body = expr_body
        self.this_val = this_val      # captured `this` for arrows
        self.interp = interp

    def __repr__(self):
        return f"JSFunction({self.name})"


class BoundMethod:
    def __init__(self, fn, this):
        self.fn = fn
        self.this = this


class JSClass:
    def __init__(self, name, methods, fields, statics):
        self.name = name
        self.methods = methods        # name -> JSFunction
        self.fields = fields          # [(name, init_expr, env)]
        self.props = statics          # static members

    def __repr__(self):
        return f"JSClass({self.name})"


class JSRegExp:
    def __init__(self, pattern: str, flags: str):
        self.source = pattern
        self.flags = flags
        pyflags = 0
        if "i" in flags:
            pyflags |= _re.IGNORECASE
        if "m" in flags:
            pyflags |= _re.MULTILINE
        if "s" in flags:
            pyflags |= _re.DOTALL
        self.re = _re.compile(_js_regex_to_py(pattern), pyflags)
        self.global_ = "g" in flags


def _js_regex_to_py(p: str) -> str:
    # the client's regexes are simple; translate the few divergences
    return p.replace(r"\d", "[0-9]").replace(r"\w", "[A-Za-z0-9_]") \
            .replace(r"\b", r"\b")


class JSPromise:
    def __init__(self, interp):
        self.interp = interp
        self.state = "pending"        # pending | fulfilled | rejected
        self.value = UNDEF
        self.callbacks: List[Tuple[Any, Any]] = []

    def resolve(self, value):
        if self.state != "pending":
            return
        if isinstance(value, JSPromise):
            value.then_native(self.resolve, self.reject)
            return
        self.state = "fulfilled"
        self.value = value
        self._flush()

    def reject(self, value):
        if self.state != "pending":
            return
        self.state = "rejected"
        self.value = value
        self._flush()

    def _flush(self):
        for on_ok, on_err in self.callbacks:
            self._schedule(on_ok, on_err)
        self.callbacks = []

    def _schedule(self, on_ok, on_err):
        def task():
            if self.state == "fulfilled" and on_ok is not None:
                on_ok(self.value)
            elif self.state == "rejected" and on_err is not None:
                on_err(self.value)
        self.interp.microtasks.append(task)

    def then_native(self, on_ok, on_err=None):
        if self.state == "pending":
            self.callbacks.append((on_ok, on_err))
        else:
            self._schedule(on_ok, on_err)


class JSArrayBuffer:
    def __init__(self, data):
        self.data = bytearray(data) if not isinstance(data, bytearray) \
            else data

    @property
    def byteLength(self):
        return float(len(self.data))

    def slice(self, start=0.0, end=None):
        n = len(self.data)
        s = int(to_num(start))
        e = n if end is None or end is UNDEF else int(to_num(end))
        if s < 0:
            s += n
        if e < 0:
            e += n
        s = max(0, min(n, s))
        e = max(s, min(n, e))
        return JSArrayBuffer(bytearray(self.data[s:e]))


_DTYPES = {"u1": ("B", 1), "i2": ("h", 2), "f4": ("f", 4)}


class JSTypedArray:
    def __init__(self, kind: str, buffer: JSArrayBuffer, offset: int = 0,
                 length: Optional[int] = None):
        self.kind = kind
        fmt, size = _DTYPES[kind]
        self.fmt, self.itemsize = fmt, size
        self.buffer = buffer
        self.offset = offset
        avail = (len(buffer.data) - offset) // size
        self.length = avail if length is None else length

    def get(self, i: int):
        if not 0 <= i < self.length:
            return UNDEF
        off = self.offset + i * self.itemsize
        return float(_struct.unpack_from(
            "<" + self.fmt, self.buffer.data, off)[0])

    def set_index(self, i: int, v: float):
        if not 0 <= i < self.length:
            return
        off = self.offset + i * self.itemsize
        if self.fmt == "B":
            v = int(v) & 0xFF
        elif self.fmt == "h":
            v = ((int(v) + 0x8000) & 0xFFFF) - 0x8000
        _struct.pack_into("<" + self.fmt, self.buffer.data, off, v)

    def tolist(self):
        return [self.get(i) for i in range(self.length)]


class JSDataView:
    def __init__(self, buffer: JSArrayBuffer, offset: int = 0,
                 length: Optional[int] = None):
        self.buffer = buffer
        self.offset = offset
        self.length = (len(buffer.data) - offset) if length is None \
            else length


class JSThrow(Exception):
    def __init__(self, value):
        self.value = value
        super().__init__(_safe_str(value))


class ReturnEx(Exception):
    def __init__(self, value):
        self.value = value


class BreakEx(Exception):
    pass


class ContinueEx(Exception):
    pass


def _safe_str(v):
    try:
        if isinstance(v, JSObject) and "message" in v.props:
            return str(v.props.get("name", "Error")) + ": " + \
                str(v.props["message"])
        return str(v)
    except Exception:
        return "<js value>"


class Env:
    __slots__ = ("vars", "parent")

    def __init__(self, parent=None, vars=None):
        self.vars = vars or {}
        self.parent = parent

    def lookup(self, name):
        e = self
        while e is not None:
            if name in e.vars:
                return e.vars[name]
            e = e.parent
        raise JSThrow(make_error("ReferenceError", f"{name} is not defined"))

    def set_existing(self, name, value) -> bool:
        e = self
        while e is not None:
            if name in e.vars:
                e.vars[name] = value
                return True
            e = e.parent
        return False

    def declare(self, name, value):
        self.vars[name] = value


def make_error(name: str, message: str) -> JSObject:
    return JSObject({"name": name, "message": message,
                     "stack": name + ": " + message})


# ========================================================== evaluator

def to_num(v) -> float:
    if isinstance(v, bool):
        return 1.0 if v else 0.0
    if isinstance(v, float):
        return v
    if isinstance(v, int):
        return float(v)
    if v is UNDEF:
        return float("nan")
    if v is None:
        return 0.0
    if isinstance(v, str):
        s = v.strip()
        if not s:
            return 0.0
        try:
            if s[:2].lower() == "0x":
                return float(int(s, 16))
            return float(s)
        except ValueError:
            return float("nan")
    return float("nan")


def to_int32(v) -> int:
    f = to_num(v)
    if f != f or f in (float("inf"), float("-inf")):
        return 0
    i = int(f) & 0xFFFFFFFF
    return i - 0x100000000 if i >= 0x80000000 else i


def to_uint32(v) -> int:
    f = to_num(v)
    if f != f or f in (float("inf"), float("-inf")):
        return 0
    return int(f) & 0xFFFFFFFF


def truthy(v) -> bool:
    if v is UNDEF or v is None:
        return False
    if isinstance(v, bool):
        return v
    if isinstance(v, float):
        return v == v and v != 0.0
    if isinstance(v, int):
        return v != 0
    if isinstance(v, str):
        return len(v) > 0
    return True


def to_str(v) -> str:
    if isinstance(v, str):
        return v
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, float):
        return _num_to_str(v)
    if isinstance(v, int):
        return _num_to_str(float(v))
    if v is UNDEF:
        return "undefined"
    if v is None:
        return "null"
    if isinstance(v, JSArray):
        return ",".join("" if (e is UNDEF or e is None) else to_str(e)
                        for e in v.elems)
    if isinstance(v, JSObject):
        if "message" in v.props and "name" in v.props:
            return f"{to_str(v.props['name'])}: {to_str(v.props['message'])}"
        return "[object Object]"
    if isinstance(v, (JSFunction, BoundMethod)):
        return "function"
    if isinstance(v, JSTypedArray):
        return ",".join(_num_to_str(x) for x in v.tolist())
    return str(v)


def strict_eq(a, b) -> bool:
    if a is UNDEF and b is UNDEF:
        return True
    if a is None and b is None:
        return True
    if isinstance(a, bool) or isinstance(b, bool):
        return isinstance(a, bool) and isinstance(b, bool) and a == b
    if isinstance(a, (int, float)) and isinstance(b, (int, float)):
        return float(a) == float(b)
    if isinstance(a, str) and isinstance(b, str):
        return a == b
    return a is b


def loose_eq(a, b) -> bool:
    if (a is UNDEF or a is None) and (b is UNDEF or b is None):
        return True
    if (a is UNDEF or a is None) or (b is UNDEF or b is None):
        return False
    if isinstance(a, str) and isinstance(b, (int, float)) \
            and not isinstance(b, bool):
        return to_num(a) == float(b)
    if isinstance(b, str) and isinstance(a, (int, float)) \
            and not isinstance(a, bool):
        return to_num(b) == float(a)
    if isinstance(a, bool) or isinstance(b, bool):
        return to_num(a) == to_num(b)
    return strict_eq(a, b)


class NativeFunction:
    """Python callable exposed to JS. fn(this, args, interp) -> value."""

    def __init__(self, fn: Callable, name: str = ""):
        self.fn = fn
        self.name = name or getattr(fn, "__name__", "")

    def __repr__(self):
        return f"NativeFunction({self.name})"


class Interp:
    def __init__(self):
        self.globals = Env()
        self.microtasks: List[Callable] = []
        self.timers: List[Tuple[float, Any, float, bool]] = []
        self._timer_id = 1
        self.timer_map: Dict[int, Tuple[Any, float, bool]] = {}
        install_stdlib(self)

    # ------------------------------------------------------ entry points

    def run(self, src: str, env: Optional[Env] = None):
        stmts = parse(src)
        env = env or self.globals
        self.hoist(stmts, env)
        result = UNDEF
        for s in stmts:
            result = self.exec_stmt(s, env)
        return result

    def run_microtasks(self, limit: int = 10000):
        n = 0
        while self.microtasks and n < limit:
            task = self.microtasks.pop(0)
            task()
            n += 1

    def fire_timers(self, count: int = 1):
        """Fire every registered interval/timeout ``count`` times (tests
        drive time manually)."""
        for _ in range(count):
            for tid in list(self.timer_map):
                entry = self.timer_map.get(tid)
                if entry is None:
                    continue
                fn, _delay, repeat = entry
                if not repeat:
                    del self.timer_map[tid]
                self.call(fn, [])
                self.run_microtasks()

    # ------------------------------------------------------ declarations

    def hoist(self, stmts, env):
        for s in stmts:
            if s[0] == "func":
                _, name, params, body, is_async = s
                env.declare(name, JSFunction(
                    name, params, body, env, is_async, False, False,
                    interp=self))
            elif s[0] == "var" and s[1] == "var":
                for target, _init in s[2]:
                    if target[0] == "ident" and target[1] not in env.vars:
                        env.declare(target[1], UNDEF)

    # ------------------------------------------------------- statements

    def exec_stmt(self, s, env):
        kind = s[0]
        if kind == "expr":
            return self.eval(s[1], env)
        if kind == "var":
            for target, init in s[2]:
                val = UNDEF if init is None else self.eval(init, env)
                self.bind_pattern(target, val, env, declare=True)
            return UNDEF
        if kind == "block":
            inner = Env(env)
            self.hoist(s[1], inner)
            for st in s[1]:
                self.exec_stmt(st, inner)
            return UNDEF
        if kind == "if":
            if truthy(self.eval(s[1], env)):
                self.exec_stmt(s[2], env)
            elif s[3] is not None:
                self.exec_stmt(s[3], env)
            return UNDEF
        if kind == "while":
            while truthy(self.eval(s[1], env)):
                try:
                    self.exec_stmt(s[2], env)
                except BreakEx:
                    break
                except ContinueEx:
                    continue
            return UNDEF
        if kind == "dowhile":
            while True:
                try:
                    self.exec_stmt(s[1], env)
                except BreakEx:
                    break
                except ContinueEx:
                    pass
                if not truthy(self.eval(s[2], env)):
                    break
            return UNDEF
        if kind == "for":
            _, init, test, update, body = s
            loop_env = Env(env)
            if init is not None:
                self.exec_stmt(init, loop_env)
            while test is None or truthy(self.eval(test, loop_env)):
                try:
                    self.exec_stmt(body, Env(loop_env))
                except BreakEx:
                    break
                except ContinueEx:
                    pass
                if update is not None:
                    self.eval(update, loop_env)
            return UNDEF
        if kind == "forof":
            _, dkind, target, iterable, body = s
            it = self.eval(iterable, env)
            for item in self.js_iter(it):
                inner = Env(env)
                self.bind_pattern(target, item, inner, declare=True)
                try:
                    self.exec_stmt(body, inner)
                except BreakEx:
                    break
                except ContinueEx:
                    continue
            return UNDEF
        if kind == "forin":
            _, dkind, target, objexpr, body = s
            obj = self.eval(objexpr, env)
            for key in self.enum_keys(obj):
                inner = Env(env)
                self.bind_pattern(target, key, inner, declare=True)
                try:
                    self.exec_stmt(body, inner)
                except BreakEx:
                    break
                except ContinueEx:
                    continue
            return UNDEF
        if kind == "switch":
            _, disc_e, cases = s
            disc = self.eval(disc_e, env)
            inner = Env(env)
            matched = False
            try:
                for test, body in cases:
                    if not matched and test is not None \
                            and strict_eq(self.eval(test, inner), disc):
                        matched = True
                    if matched:
                        for st in body:
                            self.exec_stmt(st, inner)
                if not matched:
                    seen_default = False
                    for test, body in cases:
                        if test is None:
                            seen_default = True
                        if seen_default:
                            for st in body:
                                self.exec_stmt(st, inner)
            except BreakEx:
                pass
            return UNDEF
        if kind == "try":
            _, block, param, catch, final = s
            try:
                self.exec_stmt(block, env)
            except JSThrow as ex:
                if catch is not None:
                    inner = Env(env)
                    if param is not None:
                        self.bind_pattern(param, ex.value, inner,
                                          declare=True)
                    self.exec_stmt(catch, inner)
                elif final is None:
                    raise
            finally:
                if final is not None:
                    self.exec_stmt(final, env)
            return UNDEF
        if kind == "throw":
            raise JSThrow(self.eval(s[1], env))
        if kind == "ret":
            raise ReturnEx(UNDEF if s[1] is None else self.eval(s[1], env))
        if kind == "break":
            raise BreakEx()
        if kind == "continue":
            raise ContinueEx()
        if kind == "func":
            return UNDEF          # hoisted
        if kind == "class":
            _, name, parent, methods, fields = s
            env.declare(name, self.make_class(s, env))
            return UNDEF
        if kind == "empty":
            return UNDEF
        raise RuntimeError(f"unknown statement {kind}")

    def make_class(self, s, env):
        _, name, parent, methods, fields = s
        meth = {}
        statics = {}
        inst_fields = []
        for is_static, mname, params, body, is_async in methods:
            fn = JSFunction(mname, params, body, env, is_async, False,
                            False, interp=self)
            if is_static:
                statics[mname] = fn
            else:
                meth[mname] = fn
        klass = JSClass(name, meth, inst_fields, statics)
        for is_static, fname, init in fields:
            if is_static:
                statics[fname] = UNDEF if init is None \
                    else self.eval(init, env)
            else:
                inst_fields.append((fname, init, env))
        return klass

    # ------------------------------------------------------ expressions

    def eval(self, e, env):
        kind = e[0]
        if kind == "num":
            return e[1]
        if kind == "str":
            return e[1]
        if kind == "bool":
            return e[1]
        if kind == "null":
            return None
        if kind == "undef":
            return UNDEF
        if kind == "regex":
            return JSRegExp(e[1], e[2])
        if kind == "tmpl":
            out = []
            for k, v in e[1]:
                out.append(v if k == "s" else to_str(self.eval(v, env)))
            return "".join(out)
        if kind == "ident":
            return env.lookup(e[1])
        if kind == "this":
            return env.lookup("this")
        if kind == "arr":
            elems = []
            for el in e[1]:
                if el[0] == "spread":
                    elems.extend(self.js_iter(self.eval(el[1], env)))
                else:
                    elems.append(self.eval(el, env))
            return JSArray(elems)
        if kind == "obj":
            props = {}
            for p in e[1]:
                if p[0] == "spread":
                    src = self.eval(p[1], env)
                    if isinstance(src, JSObject):
                        props.update(src.props)
                    continue
                _, kexpr, vexpr, computed = p
                key = to_str(self.eval(kexpr, env)) if computed \
                    else kexpr[1]
                props[key] = self.eval(vexpr, env)
            return JSObject(props)
        if kind == "fn":
            _, name, params, body, is_async, is_arrow, expr_body = e
            this_val = UNDEF
            if is_arrow:
                try:
                    this_val = env.lookup("this")
                except JSThrow:
                    this_val = UNDEF
            return JSFunction(name, params, body, env, is_async, is_arrow,
                              expr_body, this_val, interp=self)
        if kind == "seq":
            out = UNDEF
            for sub in e[1]:
                out = self.eval(sub, env)
            return out
        if kind == "cond":
            return self.eval(e[2], env) if truthy(self.eval(e[1], env)) \
                else self.eval(e[3], env)
        if kind == "logic":
            op = e[1]
            left = self.eval(e[2], env)
            if op == "&&":
                return self.eval(e[3], env) if truthy(left) else left
            if op == "||":
                return left if truthy(left) else self.eval(e[3], env)
            if op == "??":
                return self.eval(e[3], env) \
                    if (left is UNDEF or left is None) else left
        if kind == "bin":
            return self.binop(e[1], self.eval(e[2], env),
                              self.eval(e[3], env))
        if kind == "un":
            op = e[1]
            if op == "typeof":
                try:
                    v = self.eval(e[2], env)
                except JSThrow:
                    return "undefined"
                return js_typeof(v)
            if op == "delete":
                tgt = e[2]
                if tgt[0] == "member":
                    obj = self.eval(tgt[1], env)
                    key = to_str(self.eval(tgt[2], env))
                    if isinstance(obj, JSObject):
                        obj.props.pop(key, None)
                    elif isinstance(obj, JSArray) and key.isdigit():
                        i = int(key)
                        if 0 <= i < len(obj.elems):
                            obj.elems[i] = UNDEF
                return True
            v = self.eval(e[2], env)
            if op == "!":
                return not truthy(v)
            if op == "-":
                return -to_num(v)
            if op == "+":
                return to_num(v)
            if op == "~":
                return float(~to_int32(v))
            if op == "void":
                return UNDEF
        if kind == "update":
            _, op, prefix, target = e
            old = to_num(self.eval(target, env))
            new = old + (1.0 if op == "++" else -1.0)
            self.assign_to(target, new, env)
            return new if prefix else old
        if kind == "assign":
            _, op, target, vexpr = e
            if op == "=":
                val = self.eval(vexpr, env)
            elif op in ("&&=", "||=", "??="):
                cur = self.eval(target, env)
                if op == "&&=" and not truthy(cur):
                    return cur
                if op == "||=" and truthy(cur):
                    return cur
                if op == "??=" and not (cur is UNDEF or cur is None):
                    return cur
                val = self.eval(vexpr, env)
            else:
                cur = self.eval(target, env)
                val = self.binop(op[:-1], cur, self.eval(vexpr, env))
            self.assign_to(target, val, env)
            return val
        if kind == "member":
            _, oexpr, pexpr, computed, optional = e
            obj = self.eval(oexpr, env)
            if optional and (obj is UNDEF or obj is None):
                return UNDEF
            key = self.eval(pexpr, env)
            return self.get_prop(obj, key)
        if kind == "call":
            _, callee, args, optional = e
            if callee[0] == "member":
                obj = self.eval(callee[1], env)
                if (optional or callee[4]) and (obj is UNDEF or obj is None):
                    return UNDEF
                key = self.eval(callee[2], env)
                fn = self.get_prop(obj, key)
                if optional and (fn is UNDEF or fn is None):
                    return UNDEF
                argv = self.eval_args(args, env)
                return self.call(fn, argv, this=obj)
            fn = self.eval(callee, env)
            if optional and (fn is UNDEF or fn is None):
                return UNDEF
            argv = self.eval_args(args, env)
            return self.call(fn, argv)
        if kind == "new":
            _, cexpr, args = e
            ctor = self.eval(cexpr, env)
            argv = self.eval_args(args, env)
            return self.construct(ctor, argv)
        if kind == "await":
            v = self.eval(e[1], env)
            return self.await_value(v)
        raise RuntimeError(f"unknown expression {kind}")

    def eval_args(self, args, env) -> list:
        out = []
        for a in args:
            if a[0] == "spread":
                out.extend(self.js_iter(self.eval(a[1], env)))
            else:
                out.append(self.eval(a, env))
        return out

    def await_value(self, v):
        if isinstance(v, JSPromise):
            self.run_microtasks()
            for _ in range(10000):
                if v.state != "pending":
                    break
                if not self.microtasks:
                    raise JSThrow(make_error(
                        "Error", "await on a promise that never settles "
                        "(stub should resolve synchronously)"))
                self.run_microtasks()
            if v.state == "rejected":
                raise JSThrow(v.value)
            return v.value
        return v

    def binop(self, op, a, b):
        if op == "+":
            if isinstance(a, str) or isinstance(b, str) \
                    or isinstance(a, (JSArray, JSObject)) \
                    or isinstance(b, (JSArray, JSObject)):
                return to_str(a) + to_str(b)
            return to_num(a) + to_num(b)
        if op == "-":
            return to_num(a) - to_num(b)
        if op == "*":
            return to_num(a) * to_num(b)
        if op == "/":
            x, y = to_num(a), to_num(b)
            if y == 0:
                if x == 0 or x != x:
                    return float("nan")
                return float("inf") if x > 0 else float("-inf")
            return x / y
        if op == "%":
            x, y = to_num(a), to_num(b)
            if y == 0 or x != x or y != y:
                return float("nan")
            return _math.fmod(x, y)
        if op == "**":
            return to_num(a) ** to_num(b)
        if op == "==":
            return loose_eq(a, b)
        if op == "!=":
            return not loose_eq(a, b)
        if op == "===":
            return strict_eq(a, b)
        if op == "!==":
            return not strict_eq(a, b)
        if op in ("<", ">", "<=", ">="):
            if isinstance(a, str) and isinstance(b, str):
                return {"<": a < b, ">": a > b,
                        "<=": a <= b, ">=": a >= b}[op]
            x, y = to_num(a), to_num(b)
            if x != x or y != y:
                return False
            return {"<": x < y, ">": x > y, "<=": x <= y, ">=": x >= y}[op]
        if op == "&":
            return float(to_int32(a) & to_int32(b))
        if op == "|":
            return float(to_int32(a) | to_int32(b))
        if op == "^":
            return float(to_int32(a) ^ to_int32(b))
        if op == "<<":
            return float(to_int32(to_int32(a) << (to_uint32(b) & 31)))
        if op == ">>":
            return float(to_int32(a) >> (to_uint32(b) & 31))
        if op == ">>>":
            return float(to_uint32(a) >> (to_uint32(b) & 31))
        if op == "in":
            key = to_str(a)
            if isinstance(b, JSObject):
                return key in b.props
            if isinstance(b, JSArray):
                return key.isdigit() and int(key) < len(b.elems)
            if isinstance(b, dict):
                return key in b
            return hasattr(b, key)
        if op == "instanceof":
            if isinstance(b, JSClass):
                return isinstance(a, JSObject) and a.klass is b
            if isinstance(b, NativeFunction):
                return js_instanceof_native(a, b.name)
            return False
        raise RuntimeError(f"unknown binop {op}")

    # -------------------------------------------------- binding/assign

    def bind_pattern(self, pat, val, env, declare=False):
        kind = pat[0]
        if kind == "ident":
            if declare:
                env.declare(pat[1], val)
            elif not env.set_existing(pat[1], val):
                self.globals.declare(pat[1], val)
            return
        if kind == "arrpat":
            items = list(self.js_iter(val)) if val not in (UNDEF, None) \
                else []
            for i, el in enumerate(pat[1]):
                if el is None:
                    continue
                _, sub, default = el
                v = items[i] if i < len(items) else UNDEF
                if v is UNDEF and default is not None:
                    v = self.eval(default, env)
                self.bind_pattern(sub, v, env, declare)
            return
        if kind == "objpat":
            for name, sub, default in pat[1]:
                v = self.get_prop(val, name)
                if v is UNDEF and default is not None:
                    v = self.eval(default, env)
                self.bind_pattern(sub, v, env, declare)
            return
        raise RuntimeError(f"unknown pattern {kind}")

    def assign_to(self, target, val, env):
        if target[0] == "ident":
            if not env.set_existing(target[1], val):
                self.globals.declare(target[1], val)
            return
        if target[0] == "member":
            obj = self.eval(target[1], env)
            key = self.eval(target[2], env)
            self.set_prop(obj, key, val)
            return
        if target[0] == "arr":
            self.bind_pattern(_expr_to_pattern(target), val, env)
            return
        raise JSThrow(make_error("SyntaxError", "bad assignment target"))

    # ------------------------------------------------------- functions

    def call(self, fn, args: list, this=UNDEF):
        if isinstance(fn, BoundMethod):
            return self.call(fn.fn, args, this=fn.this)
        if isinstance(fn, NativeFunction):
            try:
                return fn.fn(this, args, self)
            except (JSThrow, ReturnEx, BreakEx, ContinueEx):
                raise
            except Exception as e:
                # host failures surface as catchable JS exceptions, the
                # way a browser API throwing does
                raise JSThrow(make_error("Error", str(e)))
        if isinstance(fn, JSFunction):
            return self.invoke(fn, args, this)
        if callable(fn):
            try:
                out = fn(*args)
            except (JSThrow, ReturnEx, BreakEx, ContinueEx):
                raise
            except Exception as e:
                raise JSThrow(make_error("Error", str(e)))
            return normalize_host(out)
        raise JSThrow(make_error("TypeError",
                                 f"{_safe_str(fn)} is not a function"))

    def invoke(self, fn: JSFunction, args: list, this=UNDEF):
        env = Env(fn.env)
        if fn.is_arrow:
            env.declare("this", fn.this_val)
        else:
            env.declare("this", this)
        i = 0
        for p in fn.params:
            if p[0] == "rest":
                env.declare(p[1], JSArray(list(args[i:])))
                break
            _, pat, default = p
            v = args[i] if i < len(args) else UNDEF
            if v is UNDEF and default is not None:
                v = self.eval(default, env)
            self.bind_pattern(pat, v, env, declare=True)
            i += 1
        try:
            if fn.expr_body:
                result = self.eval(fn.body, env)
            else:
                self.hoist(fn.body[1], env)
                for st in fn.body[1]:
                    self.exec_stmt(st, env)
                result = UNDEF
        except ReturnEx as r:
            result = r.value
        except JSThrow:
            if fn.is_async:
                p = JSPromise(self)
                import sys
                p.reject(sys.exc_info()[1].value)
                return p
            raise
        if fn.is_async:
            p = JSPromise(self)
            p.resolve(result)
            return p
        return result

    def construct(self, ctor, args: list):
        if isinstance(ctor, JSClass):
            obj = JSObject({}, klass=ctor)
            for fname, init, fenv in ctor.fields:
                fe = Env(fenv)
                fe.declare("this", obj)
                obj.props[fname] = UNDEF if init is None \
                    else self.eval(init, fe)
            ctor_fn = ctor.methods.get("constructor")
            if ctor_fn is not None:
                self.invoke(ctor_fn, args, this=obj)
            return obj
        if isinstance(ctor, NativeFunction):
            return ctor.fn(None, args, self)
        if callable(ctor):
            return normalize_host(ctor(*args))
        raise JSThrow(make_error("TypeError", "not a constructor"))

    # ------------------------------------------------------ iteration

    def js_iter(self, v):
        if isinstance(v, JSArray):
            return list(v.elems)
        if isinstance(v, str):
            return list(v)
        if isinstance(v, JSTypedArray):
            return v.tolist()
        if isinstance(v, dict):       # Map
            return [JSArray([k, val]) for k, val in v.items()]
        if isinstance(v, set):
            return list(v)
        if isinstance(v, JSObject) and "__iter__" in v.props:
            return self.call(v.props["__iter__"], [], this=v)
        if isinstance(v, (list, tuple)):
            return list(v)
        if hasattr(v, "__js_iter__"):
            return list(v.__js_iter__())
        raise JSThrow(make_error("TypeError",
                                 f"{_safe_str(v)} is not iterable"))

    def enum_keys(self, v):
        if isinstance(v, JSObject):
            return list(v.props.keys())
        if isinstance(v, JSArray):
            return [_num_to_str(float(i)) for i in range(len(v.elems))]
        if isinstance(v, dict):
            return list(v.keys())
        return []


def js_typeof(v) -> str:
    if v is UNDEF:
        return "undefined"
    if isinstance(v, bool):
        return "boolean"
    if isinstance(v, (int, float)):
        return "number"
    if isinstance(v, str):
        return "string"
    if isinstance(v, (JSFunction, NativeFunction, BoundMethod, JSClass)) \
            or callable(v):
        return "function"
    return "object"


def js_instanceof_native(v, name: str) -> bool:
    return {
        "Uint8Array": isinstance(v, JSTypedArray) and v.kind == "u1",
        "Int16Array": isinstance(v, JSTypedArray) and v.kind == "i2",
        "Float32Array": isinstance(v, JSTypedArray) and v.kind == "f4",
        "ArrayBuffer": isinstance(v, JSArrayBuffer),
        "Array": isinstance(v, JSArray),
        "Map": isinstance(v, dict),
        "Set": isinstance(v, set),
    }.get(name, False)


def normalize_host(v):
    """Host (python) return values → JS values."""
    if v is None:
        return UNDEF
    if isinstance(v, int) and not isinstance(v, bool):
        return float(v)
    if isinstance(v, (bytes, bytearray)):
        return JSArrayBuffer(bytearray(v))
    return v


# ======================================================= property layer

def _nf(fn, name=""):
    return NativeFunction(fn, name)


def _method(table, obj, key):
    fn = table.get(key)
    if fn is None:
        return None
    return BoundMethod(_nf(fn, key), obj)


def _get_prop(self, obj, key):
    if isinstance(key, float) and not isinstance(obj, (JSObject, JSClass)):
        idx = int(key)
        if isinstance(obj, JSArray):
            return obj.elems[idx] if 0 <= idx < len(obj.elems) else UNDEF
        if isinstance(obj, str):
            return obj[idx] if 0 <= idx < len(obj) else UNDEF
        if isinstance(obj, JSTypedArray):
            return obj.get(idx)
    key = to_str(key)
    if obj is UNDEF or obj is None:
        raise JSThrow(make_error(
            "TypeError", f"cannot read {key!r} of {to_str(obj)}"))
    if isinstance(obj, JSObject):
        if key in obj.props:
            v = obj.props[key]
            if isinstance(v, JSFunction) and not v.is_arrow:
                return BoundMethod(v, obj)
            return v
        k = obj.klass
        if k is not None and key in k.methods:
            return BoundMethod(k.methods[key], obj)
        if k is not None and key == "constructor":
            return k
        if key == "hasOwnProperty":
            return NativeFunction(
                lambda t, a, i, _o=obj: to_str(a[0]) in _o.props
                if a else False, "hasOwnProperty")
        return UNDEF
    if isinstance(obj, JSArray):
        if key == "length":
            return float(len(obj.elems))
        if key.lstrip("-").isdigit():
            i = int(key)
            return obj.elems[i] if 0 <= i < len(obj.elems) else UNDEF
        m = _method(ARRAY_METHODS, obj, key)
        if m:
            return m
        return UNDEF
    if isinstance(obj, str):
        if key == "length":
            return float(len(obj))
        if key.isdigit():
            i = int(key)
            return obj[i] if i < len(obj) else UNDEF
        m = _method(STRING_METHODS, obj, key)
        if m:
            return m
        return UNDEF
    if isinstance(obj, bool):
        m = _method(BOOL_METHODS, obj, key)
        if m:
            return m
        return UNDEF
    if isinstance(obj, float):
        m = _method(NUMBER_METHODS, obj, key)
        if m:
            return m
        return UNDEF
    if isinstance(obj, dict):
        if key == "size":
            return float(len(obj))
        m = _method(MAP_METHODS, obj, key)
        if m:
            return m
        return UNDEF
    if isinstance(obj, set):
        if key == "size":
            return float(len(obj))
        m = _method(SET_METHODS, obj, key)
        if m:
            return m
        return UNDEF
    if isinstance(obj, JSTypedArray):
        if key == "length":
            return float(obj.length)
        if key == "byteLength":
            return float(obj.length * obj.itemsize)
        if key == "byteOffset":
            return float(obj.offset)
        if key == "buffer":
            return obj.buffer
        if key.isdigit():
            return obj.get(int(key))
        m = _method(TYPED_METHODS, obj, key)
        if m:
            return m
        return UNDEF
    if isinstance(obj, JSArrayBuffer):
        if key == "byteLength":
            return float(len(obj.data))
        if key == "slice":
            return obj.slice          # copying slice, like the spec's
        return UNDEF
    if isinstance(obj, JSDataView):
        if key == "byteLength":
            return float(obj.length)
        if key == "buffer":
            return obj.buffer
        m = _method(DATAVIEW_METHODS, obj, key)
        if m:
            return m
        return UNDEF
    if isinstance(obj, JSPromise):
        m = _method(PROMISE_METHODS, obj, key)
        if m:
            return m
        return UNDEF
    if isinstance(obj, JSRegExp):
        if key == "source":
            return obj.source
        if key == "flags":
            return obj.flags
        m = _method(REGEX_METHODS, obj, key)
        if m:
            return m
        return UNDEF
    if isinstance(obj, (JSFunction, BoundMethod, NativeFunction)):
        if key == "name":
            return getattr(obj, "name", "")
        if key == "bind":
            def _bind(this, args, interp, _f=obj):
                bt = args[0] if args else UNDEF
                pre = list(args[1:])
                def bound(this2, args2, interp2):
                    return interp2.call(_f, pre + list(args2), this=bt)
                return _nf(bound, "bound")
            return BoundMethod(_nf(_bind, "bind"), obj)
        if key == "call":
            def _call(this, args, interp, _f=obj):
                t = args[0] if args else UNDEF
                return interp.call(_f, list(args[1:]), this=t)
            return BoundMethod(_nf(_call, "call"), obj)
        if key == "apply":
            def _apply(this, args, interp, _f=obj):
                t = args[0] if args else UNDEF
                rest = args[1] if len(args) > 1 else JSArray([])
                return interp.call(_f, list(interp.js_iter(rest)), this=t)
            return BoundMethod(_nf(_apply, "apply"), obj)
        # constructor statics (WebSocket.OPEN, Array.isArray, ...) live as
        # host attributes on the function object
        return normalize_host(getattr(obj, key, UNDEF))
    if isinstance(obj, JSClass):
        if key in obj.props:
            v = obj.props[key]
            if isinstance(v, JSFunction):
                return BoundMethod(v, obj)
            return v
        if key == "name":
            return obj.name
        return UNDEF
    # host object
    v = getattr(obj, key, UNDEF)
    return normalize_host(v)


def _set_prop(self, obj, key, val):
    if isinstance(key, float) and isinstance(obj, JSArray):
        i = int(key)
        while len(obj.elems) <= i:
            obj.elems.append(UNDEF)
        obj.elems[i] = val
        return
    if isinstance(key, float) and isinstance(obj, JSTypedArray):
        obj.set_index(int(key), to_num(val))
        return
    key = to_str(key)
    if isinstance(obj, JSObject):
        obj.props[key] = val
        return
    if isinstance(obj, JSClass):
        obj.props[key] = val
        return
    if isinstance(obj, JSArray):
        if key == "length":
            n = int(to_num(val))
            del obj.elems[n:]
            return
        if key.isdigit():
            i = int(key)
            while len(obj.elems) <= i:
                obj.elems.append(UNDEF)
            obj.elems[i] = val
            return
        return
    if isinstance(obj, JSTypedArray) and key.isdigit():
        obj.set_index(int(key), to_num(val))
        return
    if obj is UNDEF or obj is None:
        raise JSThrow(make_error(
            "TypeError", f"cannot set {key!r} of {to_str(obj)}"))
    try:
        setattr(obj, key, val)
    except (AttributeError, TypeError):
        pass


Interp.get_prop = _get_prop
Interp.set_prop = _set_prop


# ========================================================== method tables

def _arg(args, i, default=UNDEF):
    return args[i] if i < len(args) else default


# ---- strings

def _str_replace(this, args, interp):
    pat, repl = _arg(args, 0), _arg(args, 1)

    def do_repl(m):
        if isinstance(repl, (JSFunction, BoundMethod, NativeFunction)):
            groups = [m.group(0)] + [g if g is not None else UNDEF
                                     for g in m.groups()]
            return to_str(interp.call(repl, [
                g for g in groups] + [float(m.start()), this]))
        out = to_str(repl)
        out = out.replace("$&", m.group(0))
        return out

    if isinstance(pat, JSRegExp):
        count = 0 if pat.global_ else 1
        return pat.re.sub(do_repl, this, count=count)
    pat_s = to_str(pat)
    if isinstance(repl, (JSFunction, BoundMethod, NativeFunction)):
        idx = this.find(pat_s)
        if idx < 0:
            return this
        rep = to_str(interp.call(repl, [pat_s, float(idx), this]))
        return this[:idx] + rep + this[idx + len(pat_s):]
    return this.replace(pat_s, to_str(repl), 1)


def _str_replace_all(this, args, interp):
    pat = to_str(_arg(args, 0))
    repl = to_str(_arg(args, 1))
    return this.replace(pat, repl)


def _str_split(this, args, interp):
    sep = _arg(args, 0)
    if sep is UNDEF:
        return JSArray([this])
    if isinstance(sep, JSRegExp):
        return JSArray(sep.re.split(this))
    sep = to_str(sep)
    if sep == "":
        return JSArray(list(this))
    limit = _arg(args, 1)
    parts = this.split(sep)
    if limit is not UNDEF:
        parts = parts[:int(to_num(limit))]
    return JSArray(parts)


def _str_slice(this, args, interp):
    n = len(this)
    a = int(to_num(_arg(args, 0, 0.0)))
    b = _arg(args, 1)
    b = n if b is UNDEF else int(to_num(b))
    return this[slice(*_norm_range(a, b, n))]


def _norm_range(a, b, n):
    if a < 0:
        a = max(0, n + a)
    if b < 0:
        b = max(0, n + b)
    return min(a, n), min(b, n)


STRING_METHODS = {
    "charCodeAt": lambda t, a, i: (
        float(ord(t[int(to_num(_arg(a, 0, 0.0)))]))
        if 0 <= int(to_num(_arg(a, 0, 0.0))) < len(t) else float("nan")),
    "codePointAt": lambda t, a, i: (
        float(ord(t[int(to_num(_arg(a, 0, 0.0)))]))
        if 0 <= int(to_num(_arg(a, 0, 0.0))) < len(t) else UNDEF),
    "charAt": lambda t, a, i: (
        t[int(to_num(_arg(a, 0, 0.0)))]
        if 0 <= int(to_num(_arg(a, 0, 0.0))) < len(t) else ""),
    "startsWith": lambda t, a, i: t.startswith(to_str(_arg(a, 0))),
    "endsWith": lambda t, a, i: t.endswith(to_str(_arg(a, 0))),
    "includes": lambda t, a, i: to_str(_arg(a, 0)) in t,
    "indexOf": lambda t, a, i: float(t.find(to_str(_arg(a, 0)))),
    "lastIndexOf": lambda t, a, i: float(t.rfind(to_str(_arg(a, 0)))),
    "toUpperCase": lambda t, a, i: t.upper(),
    "toLowerCase": lambda t, a, i: t.lower(),
    "trim": lambda t, a, i: t.strip(),
    "padStart": lambda t, a, i: t.rjust(int(to_num(_arg(a, 0, 0.0))),
                                        to_str(_arg(a, 1, " ")) or " "),
    "padEnd": lambda t, a, i: t.ljust(int(to_num(_arg(a, 0, 0.0))),
                                      to_str(_arg(a, 1, " ")) or " "),
    "repeat": lambda t, a, i: t * int(to_num(_arg(a, 0, 0.0))),
    "substring": lambda t, a, i: _str_slice(t, a, i),
    "slice": _str_slice,
    "split": _str_split,
    "replace": _str_replace,
    "replaceAll": _str_replace_all,
    "concat": lambda t, a, i: t + "".join(to_str(x) for x in a),
    "match": lambda t, a, i: (
        (lambda m: JSArray([m.group(0)] + [g if g is not None else UNDEF
                                           for g in m.groups()])
         if m else None)(_arg(a, 0).re.search(t))
        if isinstance(_arg(a, 0), JSRegExp) else None),
    "toString": lambda t, a, i: t,
}


# ---- numbers

def _num_tostring(this, args, interp):
    base = _arg(args, 0)
    if base is UNDEF:
        return _num_to_str(this)
    b = int(to_num(base))
    n = int(this)
    if n == 0:
        return "0"
    digits = "0123456789abcdefghijklmnopqrstuvwxyz"
    neg = n < 0
    n = abs(n)
    out = []
    while n:
        out.append(digits[n % b])
        n //= b
    return ("-" if neg else "") + "".join(reversed(out))


NUMBER_METHODS = {
    "toFixed": lambda t, a, i: f"{t:.{int(to_num(_arg(a, 0, 0.0)))}f}",
    "toString": _num_tostring,
    "valueOf": lambda t, a, i: t,
}

BOOL_METHODS = {
    "toString": lambda t, a, i: "true" if t else "false",
    "valueOf": lambda t, a, i: t,
}


# ---- arrays

def _arr_sort(this, args, interp):
    cmp = _arg(args, 0)
    if cmp is UNDEF:
        this.elems.sort(key=to_str)
    else:
        import functools
        this.elems.sort(key=functools.cmp_to_key(
            lambda x, y: (lambda r: -1 if r < 0 else (1 if r > 0 else 0))(
                to_num(interp.call(cmp, [x, y])))))
    return this


def _arr_splice(this, args, interp):
    start = int(to_num(_arg(args, 0, 0.0)))
    n = len(this.elems)
    if start < 0:
        start = max(0, n + start)
    dc = _arg(args, 1)
    delete_count = n - start if dc is UNDEF else int(to_num(dc))
    removed = this.elems[start:start + delete_count]
    this.elems[start:start + delete_count] = list(args[2:])
    return JSArray(removed)


ARRAY_METHODS = {
    "push": lambda t, a, i: (t.elems.extend(a), float(len(t.elems)))[1],
    "pop": lambda t, a, i: t.elems.pop() if t.elems else UNDEF,
    "shift": lambda t, a, i: t.elems.pop(0) if t.elems else UNDEF,
    "unshift": lambda t, a, i: (t.elems.__setitem__(
        slice(0, 0), list(a)), float(len(t.elems)))[1],
    "slice": lambda t, a, i: JSArray(t.elems[slice(*_norm_range(
        int(to_num(_arg(a, 0, 0.0))),
        len(t.elems) if _arg(a, 1) is UNDEF else int(to_num(_arg(a, 1))),
        len(t.elems)))]),
    "splice": _arr_splice,
    "join": lambda t, a, i: to_str(_arg(a, 0, ",")).join(
        "" if (e is UNDEF or e is None) else to_str(e) for e in t.elems),
    "toString": lambda t, a, i: ",".join(
        "" if (e is UNDEF or e is None) else to_str(e) for e in t.elems),
    "indexOf": lambda t, a, i: float(next(
        (j for j, e in enumerate(t.elems)
         if strict_eq(e, _arg(a, 0))), -1)),
    "includes": lambda t, a, i: any(
        strict_eq(e, _arg(a, 0)) for e in t.elems),
    "find": lambda t, a, i: next(
        (e for j, e in enumerate(t.elems)
         if truthy(i.call(_arg(a, 0), [e, float(j), t]))), UNDEF),
    "findIndex": lambda t, a, i: float(next(
        (j for j, e in enumerate(t.elems)
         if truthy(i.call(_arg(a, 0), [e, float(j), t]))), -1)),
    "map": lambda t, a, i: JSArray([
        i.call(_arg(a, 0), [e, float(j), t])
        for j, e in enumerate(t.elems)]),
    "filter": lambda t, a, i: JSArray([
        e for j, e in enumerate(t.elems)
        if truthy(i.call(_arg(a, 0), [e, float(j), t]))]),
    "forEach": lambda t, a, i: ([
        i.call(_arg(a, 0), [e, float(j), t])
        for j, e in enumerate(list(t.elems))], UNDEF)[1],
    "some": lambda t, a, i: any(
        truthy(i.call(_arg(a, 0), [e, float(j), t]))
        for j, e in enumerate(t.elems)),
    "every": lambda t, a, i: all(
        truthy(i.call(_arg(a, 0), [e, float(j), t]))
        for j, e in enumerate(t.elems)),
    "reduce": lambda t, a, i: _arr_reduce(t, a, i),
    "concat": lambda t, a, i: JSArray(list(t.elems) + [
        x for arg in a
        for x in (arg.elems if isinstance(arg, JSArray) else [arg])]),
    "reverse": lambda t, a, i: (t.elems.reverse(), t)[1],
    "fill": lambda t, a, i: (t.elems.__setitem__(
        slice(None), [_arg(a, 0)] * len(t.elems)), t)[1],
    "sort": _arr_sort,
    "flat": lambda t, a, i: JSArray([
        x for e in t.elems
        for x in (e.elems if isinstance(e, JSArray) else [e])]),
    "keys": lambda t, a, i: JSArray([float(j)
                                     for j in range(len(t.elems))]),
    "entries": lambda t, a, i: JSArray([
        JSArray([float(j), e]) for j, e in enumerate(t.elems)]),
}


def _arr_reduce(t, a, i):
    fn = _arg(a, 0)
    acc = _arg(a, 1)
    start = 0
    if acc is UNDEF:
        if not t.elems:
            raise JSThrow(make_error("TypeError",
                                     "reduce of empty array"))
        acc = t.elems[0]
        start = 1
    for j in range(start, len(t.elems)):
        acc = i.call(fn, [acc, t.elems[j], float(j), t])
    return acc


# ---- Map / Set

MAP_METHODS = {
    "get": lambda t, a, i: t.get(_map_key(_arg(a, 0)), UNDEF),
    "set": lambda t, a, i: (t.__setitem__(
        _map_key(_arg(a, 0)), _arg(a, 1)), t)[1],
    "has": lambda t, a, i: _map_key(_arg(a, 0)) in t,
    "delete": lambda t, a, i: t.pop(_map_key(_arg(a, 0)), None) is not None,
    "clear": lambda t, a, i: (t.clear(), UNDEF)[1],
    "keys": lambda t, a, i: JSArray(list(t.keys())),
    "values": lambda t, a, i: JSArray(list(t.values())),
    "entries": lambda t, a, i: JSArray([
        JSArray([k, v]) for k, v in t.items()]),
    "forEach": lambda t, a, i: ([
        i.call(_arg(a, 0), [v, k, t]) for k, v in list(t.items())],
        UNDEF)[1],
}


def _map_key(k):
    """SameValueZero-ish hashable key."""
    if isinstance(k, float) and k == int(k):
        return k
    if isinstance(k, (str, float, bool, int)) or k is None or k is UNDEF:
        return k
    return id(k)


SET_METHODS = {
    "add": lambda t, a, i: (t.add(_map_key(_arg(a, 0))), t)[1],
    "has": lambda t, a, i: _map_key(_arg(a, 0)) in t,
    "delete": lambda t, a, i: (
        t.discard(_map_key(_arg(a, 0))), UNDEF)[1],
    "clear": lambda t, a, i: (t.clear(), UNDEF)[1],
    "forEach": lambda t, a, i: ([
        i.call(_arg(a, 0), [v, v, t]) for v in list(t)], UNDEF)[1],
}


# ---- typed arrays / DataView

def _typed_set(this, args, interp):
    src = _arg(args, 0)
    off = int(to_num(_arg(args, 1, 0.0)))
    vals = interp.js_iter(src)
    for j, v in enumerate(vals):
        this.set_index(off + j, to_num(v))
    return UNDEF


TYPED_METHODS = {
    "set": _typed_set,
    "subarray": lambda t, a, i: JSTypedArray(
        t.kind, t.buffer,
        t.offset + int(to_num(_arg(a, 0, 0.0))) * t.itemsize,
        (t.length if _arg(a, 1) is UNDEF else int(to_num(_arg(a, 1))))
        - int(to_num(_arg(a, 0, 0.0)))),
    "slice": lambda t, a, i: _typed_slice(t, a),
    "fill": lambda t, a, i: ([t.set_index(j, to_num(_arg(a, 0, 0.0)))
                              for j in range(t.length)], t)[1],
}


def _typed_slice(t, a):
    lo = int(to_num(_arg(a, 0, 0.0)))
    hi = t.length if _arg(a, 1) is UNDEF else int(to_num(_arg(a, 1)))
    lo, hi = _norm_range(lo, hi, t.length)
    out = JSTypedArray(t.kind, JSArrayBuffer(
        bytearray((hi - lo) * t.itemsize)))
    for j in range(hi - lo):
        out.set_index(j, t.get(lo + j))
    return out


def _dv_get(fmt, size, signed_default=False):
    def get(this, args, interp):
        off = int(to_num(_arg(args, 0, 0.0)))
        little = truthy(_arg(args, 1, False))
        endian = "<" if little else ">"
        return float(_struct.unpack_from(
            endian + fmt, this.buffer.data, this.offset + off)[0])
    return get


def _dv_set(fmt, size):
    def setter(this, args, interp):
        off = int(to_num(_arg(args, 0, 0.0)))
        val = to_num(_arg(args, 1, 0.0))
        little = truthy(_arg(args, 2, False))
        endian = "<" if little else ">"
        if fmt in ("B", "H", "I"):
            val = int(val) & ((1 << (8 * size)) - 1)
        elif fmt in ("b", "h", "i"):
            val = int(val)
        _struct.pack_into(endian + fmt, this.buffer.data,
                          this.offset + off, val)
        return UNDEF
    return setter


DATAVIEW_METHODS = {
    "getUint8": _dv_get("B", 1),
    "getInt8": _dv_get("b", 1),
    "getUint16": _dv_get("H", 2),
    "getInt16": _dv_get("h", 2),
    "getUint32": _dv_get("I", 4),
    "getInt32": _dv_get("i", 4),
    "getFloat32": _dv_get("f", 4),
    "getFloat64": _dv_get("d", 8),
    "setUint8": _dv_set("B", 1),
    "setUint16": _dv_set("H", 2),
    "setUint32": _dv_set("I", 4),
    "setInt16": _dv_set("h", 2),
    "setFloat32": _dv_set("f", 4),
}


# ---- promises

def _promise_then(this, args, interp):
    on_ok, on_err = _arg(args, 0), _arg(args, 1)
    out = JSPromise(interp)

    def ok(v):
        if on_ok is UNDEF or on_ok is None:
            out.resolve(v)
            return
        try:
            out.resolve(interp.call(on_ok, [v]))
        except JSThrow as ex:
            out.reject(ex.value)

    def err(v):
        if on_err is UNDEF or on_err is None:
            out.reject(v)
            return
        try:
            out.resolve(interp.call(on_err, [v]))
        except JSThrow as ex:
            out.reject(ex.value)

    this.then_native(ok, err)
    return out


PROMISE_METHODS = {
    "then": _promise_then,
    "catch": lambda t, a, i: _promise_then(t, [UNDEF, _arg(a, 0)], i),
    "finally": lambda t, a, i: _promise_then(
        t, [_arg(a, 0), _arg(a, 0)], i),
}


REGEX_METHODS = {
    "test": lambda t, a, i: t.re.search(to_str(_arg(a, 0))) is not None,
    "exec": lambda t, a, i: (
        (lambda m: JSArray([m.group(0)] + [
            g if g is not None else UNDEF for g in m.groups()])
         if m else None)(t.re.search(to_str(_arg(a, 0))))),
}


# ============================================================== stdlib

def install_stdlib(interp: Interp) -> None:
    g = interp.globals

    def nfg(name, fn):
        g.declare(name, _nf(fn, name))

    g.declare("undefined", UNDEF)
    g.declare("NaN", float("nan"))
    g.declare("Infinity", float("inf"))
    g.declare("globalThis", JSObject())

    # console
    logs: List[str] = []

    def _log(this, args, i):
        logs.append(" ".join(to_str(a) for a in args))
        return UNDEF

    console = JSObject({
        "log": _nf(_log, "log"), "warn": _nf(_log, "warn"),
        "error": _nf(_log, "error"), "info": _nf(_log, "info"),
        "debug": _nf(_log, "debug"),
    })
    g.declare("console", console)
    interp.console_lines = logs

    # Math
    def _m1(f):
        return lambda t, a, i: float(f(to_num(_arg(a, 0, float("nan")))))

    math_obj = JSObject({
        "abs": _nf(_m1(abs)), "floor": _nf(_m1(_math.floor)),
        "ceil": _nf(_m1(_math.ceil)),
        "round": _nf(lambda t, a, i: float(
            _math.floor(to_num(_arg(a, 0, 0.0)) + 0.5))),
        "sqrt": _nf(_m1(_math.sqrt)), "sign": _nf(_m1(
            lambda x: (x > 0) - (x < 0))),
        "trunc": _nf(_m1(_math.trunc)),
        "log2": _nf(_m1(_math.log2)), "log": _nf(_m1(_math.log)),
        "sin": _nf(_m1(_math.sin)), "cos": _nf(_m1(_math.cos)),
        "atan2": _nf(lambda t, a, i: _math.atan2(
            to_num(_arg(a, 0)), to_num(_arg(a, 1)))),
        "hypot": _nf(lambda t, a, i: _math.hypot(
            *[to_num(x) for x in a])),
        "pow": _nf(lambda t, a, i: to_num(_arg(a, 0))
                   ** to_num(_arg(a, 1))),
        "min": _nf(lambda t, a, i: min(
            (to_num(x) for x in a), default=float("inf"))),
        "max": _nf(lambda t, a, i: max(
            (to_num(x) for x in a), default=float("-inf"))),
        "random": _nf(lambda t, a, i: 0.42),   # deterministic for tests
        "PI": _math.pi, "E": _math.e,
    })
    g.declare("Math", math_obj)

    # JSON
    def js_to_py(v):
        if isinstance(v, JSArray):
            return [js_to_py(x) for x in v.elems]
        if isinstance(v, JSObject):
            return {k: js_to_py(x) for k, x in v.props.items()
                    if not isinstance(
                        x, (JSFunction, NativeFunction, BoundMethod))}
        if v is UNDEF:
            return None
        if isinstance(v, float) and v == int(v) and abs(v) < 1e15:
            return int(v)
        return v

    def py_to_js(v):
        if isinstance(v, dict):
            return JSObject({k: py_to_js(x) for k, x in v.items()})
        if isinstance(v, (list, tuple)):
            return JSArray([py_to_js(x) for x in v])
        if v is None:
            return None
        if isinstance(v, bool):
            return v
        if isinstance(v, (int, float)):
            return float(v)
        return v

    def _stringify(this, args, i):
        indent = _arg(args, 2)
        kw = {}
        if indent is not UNDEF:
            kw["indent"] = int(to_num(indent))
        return _json.dumps(js_to_py(_arg(args, 0)), **kw)

    json_obj = JSObject({
        "stringify": _nf(_stringify, "stringify"),
        "parse": _nf(lambda t, a, i: py_to_js(
            _json.loads(to_str(_arg(a, 0)))), "parse"),
    })
    g.declare("JSON", json_obj)
    interp.py_to_js = py_to_js
    interp.js_to_py = js_to_py

    # Object
    obj_ns = JSObject({
        "keys": _nf(lambda t, a, i: JSArray(
            list(interp.enum_keys(_arg(a, 0))))),
        "values": _nf(lambda t, a, i: JSArray([
            interp.get_prop(_arg(a, 0), k)
            for k in interp.enum_keys(_arg(a, 0))])),
        "entries": _nf(lambda t, a, i: JSArray([
            JSArray([k, interp.get_prop(_arg(a, 0), k)])
            for k in interp.enum_keys(_arg(a, 0))])),
        "assign": _nf(_object_assign),
        "freeze": _nf(lambda t, a, i: _arg(a, 0)),
    })
    g.declare("Object", obj_ns)

    # Array
    def _array_ctor(this, args, i):
        if len(args) == 1 and isinstance(args[0], float):
            return JSArray([UNDEF] * int(args[0]))
        return JSArray(list(args))

    def _array_from(this, args, i):
        src = _arg(args, 0)
        fn = _arg(args, 1)
        if isinstance(src, JSObject) and "length" in src.props:
            items = [UNDEF] * int(to_num(src.props["length"]))
        else:
            items = list(i.js_iter(src))
        if fn is not UNDEF:
            items = [i.call(fn, [x, float(j)])
                     for j, x in enumerate(items)]
        return JSArray(items)

    arr_ctor = _nf(_array_ctor, "Array")
    g.declare("Array", arr_ctor)
    # statics via host-attr lookup on NativeFunction
    arr_ctor.isArray = _nf(
        lambda t, a, i: isinstance(_arg(a, 0), JSArray), "isArray")
    arr_ctor.from_ = None  # placeholder (JS name "from" set below)
    setattr(arr_ctor, "from", _nf(_array_from, "from"))

    # String / Number / parse*
    str_ctor = _nf(lambda t, a, i: to_str(_arg(a, 0, "")), "String")
    str_ctor.fromCharCode = _nf(lambda t, a, i: "".join(
        chr(int(to_num(x))) for x in a), "fromCharCode")
    g.declare("String", str_ctor)

    num_ctor = _nf(lambda t, a, i: to_num(_arg(a, 0, 0.0)), "Number")
    num_ctor.isInteger = _nf(lambda t, a, i: isinstance(
        _arg(a, 0), float) and _arg(a, 0) == int(_arg(a, 0)))
    num_ctor.isFinite = _nf(lambda t, a, i: isinstance(
        _arg(a, 0), float) and _math.isfinite(_arg(a, 0)))
    num_ctor.parseFloat = _nf(lambda t, a, i: to_num(_arg(a, 0)))
    g.declare("Number", num_ctor)
    g.declare("Boolean", _nf(lambda t, a, i: truthy(_arg(a, 0))))

    def _parse_int(this, args, i):
        s = to_str(_arg(args, 0)).strip()
        base = _arg(args, 1)
        b = 10 if base is UNDEF else int(to_num(base))
        m = _re.match(r"[+-]?(0[xX][0-9a-fA-F]+|[0-9a-zA-Z]*)", s)
        try:
            return float(int(m.group(0), 16 if s[:2].lower() == "0x"
                             else b))
        except (ValueError, IndexError):
            return float("nan")

    nfg("parseInt", _parse_int)
    nfg("parseFloat", lambda t, a, i: to_num(_arg(a, 0)))
    nfg("isNaN", lambda t, a, i: to_num(_arg(a, 0)) != to_num(_arg(a, 0)))
    nfg("isFinite", lambda t, a, i: _math.isfinite(to_num(_arg(a, 0))))

    # Error constructors
    for ename in ("Error", "TypeError", "RangeError", "SyntaxError",
                  "ReferenceError"):
        def _mk_err(this, args, i, _n=ename):
            return make_error(_n, to_str(_arg(args, 0, "")))
        nfg(ename, _mk_err)

    # collections
    def _map_ctor(this, args, i):
        m = {}
        src = _arg(args, 0)
        if src is not UNDEF and src is not None:
            for pair in i.js_iter(src):
                k, v = i.js_iter(pair)[:2]
                m[_map_key(k)] = v
        return m

    def _set_ctor(this, args, i):
        s = set()
        src = _arg(args, 0)
        if src is not UNDEF and src is not None:
            for x in i.js_iter(src):
                s.add(_map_key(x))
        return s

    nfg("Map", _map_ctor)
    nfg("Set", _set_ctor)

    # typed arrays
    def _typed_ctor(kind):
        def ctor(this, args, i):
            a0 = _arg(args, 0)
            fmt, size = _DTYPES[kind]
            if isinstance(a0, float):
                return JSTypedArray(kind, JSArrayBuffer(
                    bytearray(int(a0) * size)))
            if isinstance(a0, JSArrayBuffer):
                off = int(to_num(_arg(args, 1, 0.0)))
                ln = _arg(args, 2)
                return JSTypedArray(
                    kind, a0, off,
                    None if ln is UNDEF else int(to_num(ln)))
            if a0 is UNDEF:
                return JSTypedArray(kind, JSArrayBuffer(bytearray()))
            items = [to_num(x) for x in i.js_iter(a0)]
            out = JSTypedArray(kind, JSArrayBuffer(
                bytearray(len(items) * size)))
            for j, v in enumerate(items):
                out.set_index(j, v)
            return out
        return ctor

    for name, kind in (("Uint8Array", "u1"), ("Int16Array", "i2"),
                       ("Float32Array", "f4")):
        ctor = _nf(_typed_ctor(kind), name)
        ctor.BYTES_PER_ELEMENT = float(_DTYPES[kind][1])
        g.declare(name, ctor)
    nfg("ArrayBuffer", lambda t, a, i: JSArrayBuffer(
        bytearray(int(to_num(_arg(a, 0, 0.0))))))
    nfg("DataView", lambda t, a, i: JSDataView(
        _arg(a, 0),
        int(to_num(_arg(a, 1, 0.0))),
        None if _arg(a, 2) is UNDEF else int(to_num(_arg(a, 2)))))

    # Promise
    def _promise_ctor(this, args, i):
        p = JSPromise(i)
        executor = _arg(args, 0)
        if executor is not UNDEF:
            res = _nf(lambda t2, a2, i2: (p.resolve(_arg(a2, 0)),
                                          UNDEF)[1])
            rej = _nf(lambda t2, a2, i2: (p.reject(_arg(a2, 0)),
                                          UNDEF)[1])
            try:
                i.call(executor, [res, rej])
            except JSThrow as ex:
                p.reject(ex.value)
        return p

    promise_ctor = _nf(_promise_ctor, "Promise")

    def _promise_resolve(this, args, i):
        p = JSPromise(i)
        p.resolve(_arg(args, 0))
        return p

    def _promise_all(this, args, i):
        items = list(i.js_iter(_arg(args, 0)))
        out = JSPromise(i)
        results = [UNDEF] * len(items)
        remaining = [len(items)]
        if not items:
            out.resolve(JSArray([]))
            return out
        for j, it in enumerate(items):
            if isinstance(it, JSPromise):
                def ok(v, _j=j):
                    results[_j] = v
                    remaining[0] -= 1
                    if remaining[0] == 0:
                        out.resolve(JSArray(results))
                it.then_native(ok, out.reject)
            else:
                results[j] = it
                remaining[0] -= 1
        if remaining[0] == 0:
            out.resolve(JSArray(results))
        return out

    promise_ctor.resolve = _nf(_promise_resolve, "resolve")
    promise_ctor.all = _nf(_promise_all, "all")
    promise_ctor.reject = _nf(
        lambda t, a, i: (lambda p: (p.reject(_arg(a, 0)), p)[1])(
            JSPromise(i)), "reject")
    g.declare("Promise", promise_ctor)

    # timers (manually fired from tests via interp.fire_timers)
    def _set_timer(repeat):
        def fn(this, args, i):
            cb = _arg(args, 0)
            delay = to_num(_arg(args, 1, 0.0))
            tid = i._timer_id
            i._timer_id += 1
            i.timer_map[tid] = (cb, delay, repeat)
            return float(tid)
        return fn

    nfg("setTimeout", _set_timer(False))
    nfg("setInterval", _set_timer(True))
    nfg("clearTimeout", lambda t, a, i: (
        i.timer_map.pop(int(to_num(_arg(a, 0, -1.0))), None), UNDEF)[1])
    nfg("clearInterval", lambda t, a, i: (
        i.timer_map.pop(int(to_num(_arg(a, 0, -1.0))), None), UNDEF)[1])

    # base64 (latin-1 binary strings, like the browser)
    import base64 as _b64
    nfg("btoa", lambda t, a, i: _b64.b64encode(
        to_str(_arg(a, 0)).encode("latin-1")).decode("ascii"))
    nfg("atob", lambda t, a, i: _b64.b64decode(
        to_str(_arg(a, 0))).decode("latin-1"))

    # Date.now (tests control time via interp.now_ms)
    interp.now_ms = 1_000_000.0
    date_ctor = _nf(lambda t, a, i: JSObject(
        {"getTime": _nf(lambda t2, a2, i2: i.now_ms)}), "Date")
    date_ctor.now = _nf(lambda t, a, i: i.now_ms, "now")
    g.declare("Date", date_ctor)

    def _regexp_ctor(this, args, i):
        return JSRegExp(to_str(_arg(args, 0, "")),
                        to_str(_arg(args, 1, "")))

    nfg("RegExp", _regexp_ctor)


def _object_assign(this, args, interp):
    target = _arg(args, 0)
    for src in args[1:]:
        if isinstance(src, JSObject) and isinstance(target, JSObject):
            target.props.update(src.props)
        elif isinstance(src, JSObject):
            for k, v in src.props.items():
                interp.set_prop(target, k, v)
    return target

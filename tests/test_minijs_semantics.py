"""Regression tests for minijs semantics the reference-client oracle
depends on.

Round 3 proved interpreter gaps are a product hazard: the oracle test
was red because the reference client's settings handler called
``bool.toString()`` / ``[].toString()`` / ``ArrayBuffer.slice()`` and
minijs silently returned undefined for each. These tests pin the added
semantics so they cannot regress out from under the certification.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from tools.minijs import Interp, JSArrayBuffer, UNDEF  # noqa: E402


def run(src):
    it = Interp()
    it.run("let __out; " + src)
    return it.globals.vars.get("__out")


def test_bool_tostring():
    assert run("__out = true.toString();") == "true"
    assert run("__out = false.toString();") == "false"
    # the oracle's actual shape: a settings value interpolated via toString
    assert run("let v = {value: true}; __out = '' + v.value.toString();") \
        == "true"


def test_bool_valueof():
    assert run("__out = true.valueOf();") is True


def test_array_tostring():
    assert run("__out = [1, 2, 3].toString();") == "1,2,3"
    assert run("__out = [].toString();") == ""
    # undefined/null stringify as empty slots, like join(',')
    assert run("__out = [1, undefined, null, 'x'].toString();") \
        == "1,,,x"
    # allowed-list interpolation, the sanitize log-line pattern
    assert run("let a = ['jpeg', 'x264enc']; __out = `[${a}]`;") \
        == "[jpeg,x264enc]"


def test_arraybuffer_slice_via_property():
    out = run(
        "let buf = new Uint8Array([1,2,3,4,5,6]).buffer;"
        "__out = new Uint8Array(buf.slice(2));")
    assert bytes(out.buffer.data) == bytes([3, 4, 5, 6])
    out = run(
        "let buf = new Uint8Array([1,2,3,4,5,6]).buffer;"
        "__out = new Uint8Array(buf.slice(1, 3));")
    assert bytes(out.buffer.data) == bytes([2, 3])


def test_arraybuffer_slice_is_copy():
    it = Interp()
    it.run(
        "let src = new Uint8Array([9, 9]);"
        "let cut = src.buffer.slice(0);"
        "src[0] = 1;"
        "let got = new Uint8Array(cut)[0];")
    assert it.globals.vars["got"] == 9.0

"""Registry smoke (the CI registry-smoke job's script): import `repro`,
list the kernel registry, and dispatch every registered kernel at TINY
size on CPU interpret, checking each against its reference version.

    PYTHONPATH=src python examples/registry_smoke.py

Exits nonzero if a family is missing, a dispatch fails, or a kernel
disagrees with its reference — the cheapest end-to-end proof that a new
kernel actually joined the dispatch/tune/bench plumbing.
"""

import numpy as np

import repro
from repro import backend
from repro.kernels import api


def check(name: str, outs, refs, atol: float) -> None:
    for o, r in zip(outs, refs):
        err = float(np.max(np.abs(np.asarray(o) - np.asarray(r))))
        assert err <= atol, (name, err)
    print(f"  {name}: ok (atol {atol})")


def main():
    backend.enable_compile_cache()
    names = repro.list_kernels()
    print(f"registered kernels: {names}")
    assert {"gpp", "flash", "ssm", "paged_decode"} <= set(names), names

    # gpp at TINY vs the complex128 oracle
    from repro.kernels.gpp import problem, ref
    inputs = problem.make_inputs(problem.TINY)
    ar, xr = ref.ref_numpy(inputs)
    a, x = repro.dispatch("gpp", inputs, interpret=True)
    check("gpp v10@tiny", (a, x), (ar, xr),
          atol=1e-4 * float(np.max(np.abs(ar))))

    # flash + ssm: default (tuned pallas) vs their "ref" version, on tiny
    # synthetic inputs from each kernel's own make_example
    from repro.kernels.flash.kernel_def import FlashKey
    fkey = FlashKey(b=2, h=4, kvh=2, sq=64, skv=64, hd=16)
    fargs, fkw = api.get_kernel("flash").make_example(fkey)
    out = repro.dispatch("flash", *fargs, interpret=True, **fkw)
    out_ref = repro.dispatch("flash", *fargs, version="ref", **fkw)
    check("flash pallas@64", (out,), (out_ref,), atol=2e-2)

    from repro.kernels.ssm.kernel_def import SsmKey
    skey = SsmKey(b=2, t=32, c=8, n=4)
    sargs, _ = api.get_kernel("ssm").make_example(skey)
    y, hT = repro.dispatch("ssm", *sargs, interpret=True)
    y_ref, hT_ref = repro.dispatch("ssm", *sargs, version="ref")
    check("ssm pallas@32", (y, hT), (y_ref, hT_ref), atol=1e-3)

    # paged_decode: block-table gather decode vs its gather+oracle ref
    from repro.kernels.paged.kernel_def import PagedKey
    pkey = PagedKey(b=2, h=2, kvh=2, page=16, npt=4, hd=32)
    pargs, pkw = api.get_kernel("paged_decode").make_example(pkey)
    pd = repro.dispatch("paged_decode", *pargs, interpret=True, **pkw)
    pd_ref = repro.dispatch("paged_decode", *pargs, version="ref", **pkw)
    check("paged_decode gather@16x4", (pd,), (pd_ref,), atol=1e-2)

    print("registry smoke: all kernels dispatch and match their references")


if __name__ == "__main__":
    main()

"""Figure 10: binary sizes — serialized executables of the DigitRec
migratable function for (i) single-target x86, (ii) the traditional
x86+FPGA pair, and (iii) the full Xar-Trek multi-target binary.

Multi-target is necessarily the largest (it subsumes both baselines);
reported in bytes via jax.export serialization.
"""
import jax
import jax.numpy as jnp

from benchmarks.common import Timer, emit
from repro.core.binary import MultiTargetBinary
from repro.core.function import FunctionRegistry, MigratableFunction
from repro.core.targets import TargetKind
from repro.kernels import ops, ref
from repro.launch import compile_cache


def _host_knn(test, train, labels):
    d = ref.hamming_ref(test, train)
    _, idx = jax.lax.top_k(-d, 3)
    votes = labels[idx]
    counts = jax.vmap(lambda v: jnp.bincount(v, length=10))(votes)
    return jnp.argmax(counts, -1).astype(jnp.int32)


def _aux_knn(test, train, labels):           # "ARM": same ref, alt target
    return _host_knn(test, train, labels)


def _accel_knn(test, train, labels):
    return ops.knn_digits(test, train, labels)


def main() -> None:
    key = jax.random.PRNGKey(0)
    test = jax.random.randint(key, (64, 7), 0, 2**31 - 1,
                              jnp.int32).astype(jnp.uint32)
    train = jax.random.randint(key, (512, 7), 0, 2**31 - 1,
                               jnp.int32).astype(jnp.uint32)
    labels = jax.random.randint(key, (512,), 0, 10, jnp.int32)
    specs = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype)
                  for x in (test, train, labels))

    def sizes_for(variants) -> int:
        reg = FunctionRegistry()
        fn = MigratableFunction("knn", "digitrec", variants)
        reg.register(fn)
        binary = MultiTargetBinary(fn)
        with Timer() as t:
            s = binary.serialized_sizes(*specs)
        return sum(s.values()), t.us

    x86_only, us1 = sizes_for({TargetKind.HOST: _host_knn})
    x86_fpga, us2 = sizes_for({TargetKind.HOST: _host_knn,
                               TargetKind.ACCEL: _accel_knn})
    xartrek, us3 = sizes_for({TargetKind.HOST: _host_knn,
                              TargetKind.AUX: _aux_knn,
                              TargetKind.ACCEL: _accel_knn})
    emit("fig10/x86_only", us1, f"{x86_only}B")
    emit("fig10/x86_fpga", us2, f"{x86_fpga}B "
         f"(+{100*(x86_fpga-x86_only)/x86_only:.0f}% vs x86)")
    emit("fig10/xartrek_multi", us3, f"{xartrek}B "
         f"(+{100*(xartrek-x86_only)/x86_only:.0f}% vs x86; subsumes both)")
    assert xartrek >= x86_fpga >= x86_only


if __name__ == "__main__":
    compile_cache.enable()
    main()

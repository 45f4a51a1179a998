"""Record the small chip trace that `test_bench_regions.py` reads.

    python3 bench/tests/data/record_regions.py <out dir>

On one TPU: a granite-moe-1b-a400m-tiny step, 4 agents on a ring, the
concat kernel path, 2 steps per dispatch, fed by `data.prefetch_chunks`.
After one warm dispatch, five dispatches (10 steps) and the wait for the
last one are traced inside a ``bench.window`` host span, as `bench/run.py`
does; the prefetcher made the last two chunks inside the trace.
Writes ``regions_chip.xplane.pb`` (committed gzipped) and the compiled
step's text, ``regions_chip.hlo.txt.gz`` (committed without its source
tables and ``stack_frame_id``s, which name the recording machine's
paths), to the directory given.
"""
import gzip
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

FLAGS = ["--agents", "4", "--unroll-k", "2", "--per-agent-batch", "1",
         "--seq-len", "128"]


def main(out: Path) -> int:
    import jax

    from bench import run as R
    from bench import trace as T
    from repro.configs import get_config
    from repro.core import init_state
    from repro.data import make_lm_pipeline, make_placer, prefetch_chunks
    from repro.launch.steps import per_step_keys
    from repro.launch.train import build_parser
    if jax.devices()[0].platform != "tpu":
        print("record_regions: no TPU", file=sys.stderr)
        return 1
    cfg = get_config("granite-moe-1b-a400m-tiny")
    pargs = build_parser().parse_args(FLAGS)
    K = pargs.unroll_k
    prog = R.make_program(cfg, pargs)
    bundle, scanned = prog.bundle, prog.scanned
    state = init_state(bundle.init(jax.random.key(0)), pargs.agents)
    pipe = make_lm_pipeline(cfg.vocab_size, pargs.agents,
                            pargs.per_agent_batch, pargs.seq_len, seed=0)
    key = jax.random.key(1)
    out.mkdir(parents=True, exist_ok=True)
    with prefetch_chunks(pipe, K, place=make_placer(None)) as chunks:
        chunk = next(chunks)
        compiled = scanned.lower(state, chunk, per_step_keys(key, 0, K)
                                 ).compile()
        (out / "regions_chip.hlo.txt.gz").write_bytes(
            gzip.compress(compiled.as_text().encode(), mtime=0))
        state, aux = compiled(state, chunk, per_step_keys(key, 0, K))
        jax.block_until_ready(state)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        tmp = tempfile.mkdtemp()
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(1, 6):
                with jax.profiler.TraceAnnotation("bench.next_chunk"):
                    chunk = next(chunks)
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    state, aux = compiled(state, chunk,
                                          per_step_keys(key, i * K, K))
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready(state)
        jax.profiler.stop_trace()
    shutil.copy(T.find_xplane(tmp), out / "regions_chip.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    print("record_regions: wrote", sorted(p.name for p in out.iterdir()))
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))

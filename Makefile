PY := python
export PYTHONPATH := src

.PHONY: test test-fast bench bench-smoke chaos-smoke

test:
	$(PY) -m pytest -q

# skip the long distributed/serving tests (marked @pytest.mark.slow)
test-fast:
	$(PY) -m pytest -q -m "not slow"

bench:
	$(PY) -m benchmarks.run

# minutes-scale benchmark pass (CI): tiny substrate, then assert every
# JSON artifact parses and BENCH_kernels.json carries the packed-sort /
# chunk-x-blk_l sweep schema
bench-smoke:
	$(PY) -m benchmarks.run --smoke
	$(PY) -c "import json; \
	  [json.load(open('artifacts/BENCH_' + n + '.json')) \
	   for n in ('table2', 'serving')]; \
	  d = json.load(open('artifacts/BENCH_kernels.json')); \
	  assert {'rows', 'fused_sweep', 'sort', 'backend'} <= d.keys(); \
	  assert d['fused_sweep'], 'empty fused sweep'; \
	  assert all({'chunk', 'blk_l', 'us', 'delta'} \
	             <= r.keys() for r in d['fused_sweep']); \
	  assert any(r['delta'] for r in d['fused_sweep']), \
	         'no in-kernel-delta row'; \
	  s = d['sort']; \
	  assert s['packed_us'] > 0 and s['tagged_us'] > 0; \
	  sv = json.load(open('artifacts/BENCH_serving.json')); \
	  gap = sv['live_stream']['recall_gap']; \
	  assert gap <= 0.01, f'live-stream recall gap {gap} > 1%'; \
	  r = json.load(open('artifacts/BENCH_resilience.json')); \
	  rb = r['rebuild']; \
	  assert {'crash_boundaries', 'swap_race', 'drift'} <= rb.keys(); \
	  assert all({'failpoint', 'resolution', 'bit_identical', \
	              'recovery_ms'} <= b.keys() \
	             for b in rb['crash_boundaries']); \
	  assert {'fenced', 'lost_mutations', 'recovered_bit_identical'} \
	         <= rb['swap_race'].keys(); \
	  assert {'recall_fixed', 'recall_rebuilt', 'rebuilds_triggered', \
	          'recall_restored'} <= rb['drift'].keys(); \
	  print('bench artifacts OK')"

# seeded chaos drills on a tiny substrate: crash + WAL recovery must be
# bit-identical (including at every rebuild boundary), the rebuild
# swap race must be epoch-fenced, and the drift drill must show the
# rebuild restoring recall
chaos-smoke:
	$(PY) -m repro.launch.serve --chaos --n-docs 4000 --queries 64 \
	  --clusters 32 --dim 24 --n-probe 16 --k 10
	$(PY) -c "import json; \
	  d = json.load(open('artifacts/BENCH_resilience.json')); \
	  assert d['recovery']['bit_identical'], 'recovery not bit-identical'; \
	  assert d['recovery']['crashes'] > 0, 'no crashes injected'; \
	  assert len(d['deadline_curve']) > 0, 'empty deadline curve'; \
	  assert d['shard_faults']['attempts'] > 0, 'shard drill did not run'; \
	  rb = d['rebuild']; \
	  bs = rb['crash_boundaries']; \
	  assert len(bs) == 6, 'rebuild boundaries missing'; \
	  assert all(b['bit_identical'] for b in bs), \
	         'rebuild-crash recovery not bit-identical'; \
	  assert {'aborted', 'committed'} \
	         == {b['resolution'] for b in bs}, 'both windows required'; \
	  sr = rb['swap_race']; \
	  assert sr['fenced'] and sr['lost_mutations'] == 0 \
	         and sr['recovered_bit_identical'], 'swap race not fenced'; \
	  dr = rb['drift']; \
	  assert dr['rebuilds_triggered'] > 0 and dr['recall_restored'], \
	         'drift rebuild did not restore recall'; \
	  print('chaos artifact OK')"

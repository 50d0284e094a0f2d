#!/bin/bash
# proof sets for one cell: two sets of six runs on the same seeds, then three traced runs
cell=$1; secs=$2; out=chiprun_out/proof/$cell; mkdir -p $out
for set in a b; do for s in 2147480011 2147480023 2147480037 2147480041 2147480059 2147480063; do
  python3 benchmark/run.py --workload $cell --seed $s --seconds $secs --trace 0 > $out/$set.$s.out 2> $out/$set.$s.err
  echo "$cell $set $s rc=$? $(tail -1 $out/$set.$s.out | cut -c1-420)"
done; done
for s in 2147480071 2147480077 2147480081; do
  python3 benchmark/run.py --workload $cell --seed $s --seconds $secs --trace 1 --keep-trace $out/traces > $out/t.$s.out 2> $out/t.$s.err
  echo "$cell trace $s rc=$? $(tail -1 $out/t.$s.out | cut -c1-1500)"
done

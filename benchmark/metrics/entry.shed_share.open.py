"""`entry.shed_share` in an open-loop cell below its knee, which bounds no TTFT
statistic (PERF.md section 2): there it moves `tokens_per_s`, the offered
load less what the window's end cuts off. The same reading, split by the
end-to-end metric its cells report."""

from benchmark import cells

read = cells.load_reader("entry.shed_share").read

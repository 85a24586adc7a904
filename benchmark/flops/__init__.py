"""Operations and bytes from shapes alone: a model's per trained token, a
kernel's per call. Kept with the benchmark so that no PR that claims a gain
can move what "100%" means."""

package paddle

// End-to-end: save a tiny model with python, load+run it through the Go
// wrapper (reference goapi config_test.go pattern).  Requires
// libpaddle_tpu_infer.so (make -C ../csrc inference) — see README.md.

import (
	"os"
	"os/exec"
	"path/filepath"
	"testing"
)

func TestPredictorEndToEnd(t *testing.T) {
	dir := t.TempDir()
	model := filepath.Join(dir, "model")
	py := `
import sys
import paddle_tpu as paddle
from paddle_tpu import static
prefix = sys.argv[1]
paddle.enable_static()
main = static.Program()
with static.program_guard(main):
    x = static.data("x", [None, 4], "float32")
    out = static.nn.fc(x, 3)
exe = static.Executor()
static.save_inference_model(prefix, [x], [out], exe, program=main)
`
	cmd := exec.Command("python", "-c", py, model)
	cmd.Env = append(os.Environ(), "JAX_PLATFORMS=cpu")
	if out, err := cmd.CombinedOutput(); err != nil {
		t.Fatalf("model save failed: %v\n%s", err, out)
	}

	cfg := NewConfig()
	cfg.SetModel(model, "")
	if cfg.ModelDir() != model {
		t.Fatalf("ModelDir mismatch: %q", cfg.ModelDir())
	}
	pred, err := NewPredictor(cfg)
	if err != nil {
		t.Fatal(err)
	}
	defer pred.Destroy()

	if pred.GetInputNum() != 1 {
		t.Fatalf("want 1 input, got %d", pred.GetInputNum())
	}
	in := pred.GetInputHandle(pred.GetInputNames()[0])
	defer in.Destroy()
	in.Reshape([]int32{2, 4})
	if err := in.CopyFromCpu([]float32{1, 2, 3, 4, 5, 6, 7, 8}); err != nil {
		t.Fatal(err)
	}
	if err := pred.Run(); err != nil {
		t.Fatal(err)
	}
	out := pred.GetOutputHandle(pred.GetOutputNames()[0])
	defer out.Destroy()
	shape := out.Shape()
	if len(shape) != 2 || shape[0] != 2 || shape[1] != 3 {
		t.Fatalf("bad output shape %v", shape)
	}
	got := make([]float32, 6)
	if err := out.CopyToCpu(got); err != nil {
		t.Fatal(err)
	}
	for i, v := range got {
		if v != v { // NaN
			t.Fatalf("NaN at %d: %v", i, got)
		}
	}
}

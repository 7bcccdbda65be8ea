"""``benchmarks/step_text.py``'s digest: a program's text hashes the same
wherever its source lines lie and differently once a name or an operation
changes, inside a Mosaic kernel's body too."""

import base64
import io
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "benchmarks"))
import step_text  # noqa: E402


def _body(line, op="test.add"):
    """A kernel body as a custom call carries it: MLIR bytecode, base64."""
    from jax._src.lib.mlir import ir

    with ir.Context() as context:
        context.allow_unregistered_dialects = True
        module = ir.Module.parse(
            f'module {{ "{op}"() : () -> () loc("/x/kernel.py":{line}:3) }}')
        raw = io.BytesIO()
        module.operation.write_bytecode(raw)
    return base64.b64encode(raw.getvalue()).decode()


def _program(path, line, frame, scope="moe_route", body_line=7,
             body_op="test.add", quote='"'):
    return (
        "HloModule jit_step, is_scheduled=true\n\n"
        f'FileNames\n1 "{path}"\n\nFunctionNames\n1 "step"\n\n'
        f"FileLocations\n{frame} {{file_name_id=1 function_name_id=1 "
        f"line={line} end_line={line} column=1 end_column=9}}\n\n"
        f"StackFrames\n{frame} {{file_location_id={frame} "
        "parent_frame_id=1}\n\n\n"
        "ENTRY %main {\n"
        f'  %add.1 = f32[] add(%x, %y), metadata={{op_name="jit(step)/{scope}'
        f'/add" stack_frame_id={frame}}}\n'
        '  %hvt_moe_kth.1 = f32[8] custom-call(%x), custom_call_target='
        '"tpu_custom_call", backend_config={"custom_call_config":'
        f'{{{quote}body{quote}:{quote}{_body(body_line, body_op)}{quote}}}}}\n'
        "}\n")


def test_digest_drops_places_and_keeps_names_and_operations():
    here, _ = step_text.digest(_program("/root/repo/a.py", 12, 3))
    moved = _program("/elsewhere/b.py", 345, 17, body_line=99)
    assert moved != _program("/root/repo/a.py", 12, 3)
    assert step_text.digest(moved) == (here, 1)
    # the lowered text's quotes are escaped, and a space follows the colon
    lowered = lambda line: _program("/a.py", 1, 1, body_line=line,
                                    quote='\\22').replace(
                                        "body\\22:", "body\\22: ")
    assert step_text.digest(lowered(5)) == step_text.digest(lowered(6))
    for other in (_program("/root/repo/a.py", 12, 3, scope="moe_combine"),
                  _program("/root/repo/a.py", 12, 3, body_op="test.sub"),
                  _program("/root/repo/a.py", 12, 3).replace("add(", "sub(")):
        assert step_text.digest(other)[0] != here
